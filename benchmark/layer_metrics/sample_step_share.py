"""Share of ``jit_replay_train``'s device time under the scope ``sample``:
drawing ``fused_steps`` batches of windows from the rings.  Its parts go to
the run's notes in milliseconds a program run: ``sample_draw`` (eligibility
and the inverse-CDF draw), ``sample_rows`` (the ring gathers and their
unpacking), ``sample_obs`` (the observation rebuild and its masking); what is
left of ``sample`` is the player pick, the masks and the returns."""

from benchmark import harness
from handyrl_tpu.runtime import device_replay


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "program_phases.py"))
    loop = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    program = loop.find(run, device_replay, "TRAIN_PROGRAM")
    return shared.share(run, program, shared.SAMPLE, noted=(shared.SAMPLE_PARTS,))
