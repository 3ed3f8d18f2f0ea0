"""Share of the slots a whole-window net's mixers ran over that carry no
token: 100 x (1 - ``counter_observed_steps`` / ``counter_packed_slots``),
from the step's own counters (means over the window's updates).  What
``put_batch``'s packed bound still leaves of the window's padding."""


def read(run):
    slots = run.counters.get("counter_packed_slots")
    observed = run.counters.get("counter_observed_steps")
    if not slots or observed is None:
        return None
    return 100.0 * (1.0 - observed / slots)
