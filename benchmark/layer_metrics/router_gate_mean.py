"""The mean gate a token's expert term was scaled by, from the step's own
counter (``counter_router_gate_mean``, the mean over the window's updates):
the chosen expert's probability under an MLP router whose gates are not
renormalised.  Strictly inside (1 / n_experts, 1) while the router is live:
1 / n_experts is a router that tells no expert from another, 1 one whose
softmax has saturated (and whose gradient is gone)."""


def read(run):
    return run.counters.get("counter_router_gate_mean")     # None: a program without the counter
