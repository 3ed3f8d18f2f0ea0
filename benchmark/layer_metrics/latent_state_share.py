"""What the latent attention layers keep of a step, as a percentage of what
keys and values a head would be, from the step's own counters
(``counter_latent_state_values`` over ``counter_expanded_state_values``: the
values handed from burn-in to the forward part, and what every head's key of
both parts and value of the same steps would hold).  The widths fix it while
the state is the latent; it moves only if the state stops being one."""


def read(run):
    kept = run.counters.get("counter_latent_state_values")
    whole = run.counters.get("counter_expanded_state_values")
    if kept is None or not whole:       # a program without the counters, or nothing handed on
        return None
    return 100.0 * kept / whole
