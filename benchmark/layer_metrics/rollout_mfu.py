"""Model FLOP/s utilization of acting: the forward operations the tokens of
the window needed (``flops/<family>.py`` ``act_step``: only the rows that
carry an observation count) a second on the host's clock, over chips times
the peak bf16 rate.  The share of the whole step; at tens of rows a step is
bound by the bytes of its weights and state, so it is small by nature."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "actor_step.py"))
    steps, window = run.counters.get("game_steps"), run.counters.get("window_s")
    work = shared.work(run)
    if not steps or not window or not work:
        return None
    peak = run.peaks()["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * work["flops"] / work["tokens"] * (steps / window) / peak
