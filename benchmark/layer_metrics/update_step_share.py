"""Share of the train program's device time (``jit__step``; ``jit_replay_train``
in the loop) under the scope ``opt_update``: clip, decay, Adam, the ``-lr``
scale, the add, the gradient's norm and the sentinel's select over params
and optimizer state."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "program_phases.py"))
    return shared.share(run, shared.train_program(run), shared.UPDATE)
