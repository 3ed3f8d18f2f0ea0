"""Share of the traced window the chip spent in the streaming rollout
program (``jit_device_rollout``: lanes x k game steps of self-play a
dispatch)."""

from benchmark import harness
from handyrl_tpu.runtime import device_rollout


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = shared.find(run, device_rollout, "STREAM_PROGRAM")
    if program is None:
        return None
    return 100.0 * program["seconds"] / run.reduced["window_s"]
