"""Share of the routed experts' row-buffer slots that carry a row: 100 x
``counter_rows_held`` / ``counter_buffer_slots``, from the step's own
counters (means over the window's updates).  The buffers are sized for 2.5
uniform shares of rows; since PR 60 the grouped kernels run only the blocks
that hold a row (``counter_slots_run``, in the run's counters), so an empty
slot costs the route's passes over the buffer and no product, and a step's
time follows the routing.  ``counter_expert_passes``
(passes over a buffer past the first: 0 when every buffer sufficed) goes to
the notes."""


def read(run):
    rows = run.counters.get("counter_rows_held")
    slots = run.counters.get("counter_buffer_slots")
    if rows is None or not slots:
        return None     # a program without the counter: nothing to read
    run.notes["expert_buffer"] = {
        "rows_held": rows, "buffer_slots": slots,
        "expert_passes": run.counters.get("counter_expert_passes")}
    return 100.0 * rows / slots
