"""Device milliseconds of one sampled SGD update in the loop: the fused
sample+train program's device time over its executions times the updates
one execution fuses, over the executions that lie wholly inside the traced
window (one that the window's edge cuts would count as a run and bring
part of its time).  Not ``train_step_device_ms``: this program also holds
the sampler."""

from benchmark import harness
from handyrl_tpu.runtime import device_replay


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = shared.find(run, device_replay, "TRAIN_PROGRAM")
    if program is None or not program["whole_runs"]:
        return None
    fused = run.counters.get("fused_steps", 1)
    return 1e3 * program["whole_seconds"] / (program["whole_runs"] * fused)
