"""Not a metric: what the readers of the loop's device programs share.

The program names its jitted functions itself: a module-level constant
beside each ``jax.jit`` call (``device_rollout.STREAM_PROGRAM``,
``device_replay.TRAIN_PROGRAM``, ...), which a profile shows as
``jit_<constant>(<fingerprint>)``.  A reader imports the constant instead of
repeating the name, so a rename in the program cannot orphan it; a program
that has no such constant yet (an older commit) answers ``None``."""


def find(run, owner, constant):
    """``run.program_named`` the XLA program that the program's module
    ``owner`` names in ``constant``: its device seconds and executions
    inside the traced window, clipped and whole; None where the trace, the
    constant or the program is missing."""
    name = getattr(owner, constant, None)
    return None if name is None else run.program_named("jit_" + name)
