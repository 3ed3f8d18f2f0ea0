"""Share of the routed experts' row-buffer slots that carry a row: 100 x
``counter_rows_held`` / ``counter_buffer_slots``, from the step's own
counters (means over the window's updates).  The program computes every
slot of its buffers, filled or not, so that its time does not follow the
routing: this is what that costs.  ``counter_expert_passes`` (passes over a
buffer past the first: 0 when every buffer sufficed) goes to the notes."""


def read(run):
    rows = run.counters.get("counter_rows_held")
    slots = run.counters.get("counter_buffer_slots")
    if rows is None or not slots:
        return None     # a program without the counter: nothing to read
    run.notes["expert_buffer"] = {
        "rows_held": rows, "buffer_slots": slots,
        "expert_passes": run.counters.get("counter_expert_passes")}
    return 100.0 * rows / slots
