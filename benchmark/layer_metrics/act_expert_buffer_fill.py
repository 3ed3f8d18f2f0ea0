"""Share of the routed experts' row-buffer slots that carry a row when
acting: 100 x ``counter_rows_held`` / ``counter_buffer_slots``, what the net's
step mode counted over a dispatch (every routed layer, every step, every row
the rollout applies the net to; the actor loop's ``actor.counters`` event,
a mean over the window's dispatches).  The buffer is sized in blocks of 128
rows an expert, for training's hundreds of rows an expert; acting has about
nine."""


def read(run):
    rows = run.counters.get("counter_rows_held")
    slots = run.counters.get("counter_buffer_slots")
    if rows is None or not slots or "dispatches" not in run.counters:
        return None     # no acting loop, or a program without the counters
    run.notes["act_expert_buffer"] = {"rows_held": rows, "buffer_slots": slots}
    return 100.0 * rows / slots
