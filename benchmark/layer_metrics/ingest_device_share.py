"""Share of the traced window the chip spent in the replay rings' ingest
program (``jit_ingest``): folding one rollout dispatch's records into the
rings."""

from benchmark import harness
from handyrl_tpu.runtime import device_replay


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = shared.find(run, device_replay, "INGEST_PROGRAM")
    if program is None:
        return None
    return 100.0 * program["seconds"] / run.reduced["window_s"]
