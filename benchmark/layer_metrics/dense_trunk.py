"""What the readers of the dense trunk's scopes (``mlp_roofline``,
``attn_step_share``, ``norm_step_share``) share: the scope's name, from the
constant the program keeps beside its ``jax.named_scope``
(``handyrl_tpu/models/hybrid.py``).  A program that has no such constant (an
older commit's) has no such scope: the reader then answers ``None``.  Not a
metric: asked as one it answers ``None``."""


def scope_name(constant):
    from handyrl_tpu.models import hybrid

    return getattr(hybrid, constant, None)


def ms_per_step(run, scope):
    """Device milliseconds a run of the train program spends under ``scope``."""
    inside, program = run.scope(scope) if scope else None, run.program("train")
    if inside is None or program is None or not program["runs"]:
        return None
    return 1e3 * inside["seconds"] / program["runs"]


def read(run):
    return None
