"""Device milliseconds of one rollout dispatch: the streaming rollout
program's device time over its executions, of those that lie wholly inside
the traced window."""

from benchmark import harness
from handyrl_tpu.runtime import device_rollout


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = shared.find(run, device_rollout, "STREAM_PROGRAM")
    if program is None or not program["whole_runs"]:
        return None
    return 1e3 * program["whole_seconds"] / program["whole_runs"]
