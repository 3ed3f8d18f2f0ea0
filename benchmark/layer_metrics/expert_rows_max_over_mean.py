"""Imbalance of the routed rows over the held experts: the most rows any
held expert of any layer computed in an update over the mean, from the
step's own counters (1 = a uniform router)."""


def read(run):
    most = run.counters.get("counter_expert_rows_max")
    mean = run.counters.get("counter_expert_rows_mean")
    if not most or not mean:
        return None
    return most / mean
