"""Share of the traced window the chip spent in the replay's fused
sample+train program (``jit_replay_train``: ``fused_steps`` windows drawn
from the rings and as many SGD updates, in one dispatch)."""

from benchmark import harness
from handyrl_tpu.runtime import device_replay


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = shared.find(run, device_replay, "TRAIN_PROGRAM")
    if program is None:
        return None
    return 100.0 * program["seconds"] / run.reduced["window_s"]
