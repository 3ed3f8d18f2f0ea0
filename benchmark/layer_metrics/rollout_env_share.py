"""Share of ``jit_device_rollout``'s device time the environment takes: under
the scopes ``env_reset`` (finished lanes start again), ``env_observe`` (the
observation planes and their flatten) and ``env_step`` (the compact record,
the transition, the outcome).  Each of the program's five scopes goes to the
run's notes in milliseconds a dispatch (``rollout_policy`` and
``rollout_act`` are the other two)."""

from benchmark import harness
from handyrl_tpu.runtime import device_rollout


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "program_phases.py"))
    loop = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = loop.find(run, device_rollout, "STREAM_PROGRAM")
    return shared.share(run, program, shared.ROLLOUT_ENV, noted=(shared.ROLLOUT,))
