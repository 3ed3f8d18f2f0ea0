"""What the readers of the actor cell's rollout step (``rollout_mfu``,
``rollout_roofline_share``, ``rollout_experts_share``, ``rollout_state_share``)
share.  Not a metric: asked as one it answers ``None``.

The step is one iteration of the streaming rollout's scan; what it needs is
``act_step(config, cell)`` of the configuration's ``flops`` file (None where
that file counts no acting step).  The rollout program is found by the
constant the program keeps beside its ``jax.jit`` (``loop_program.find``), the
scopes by the names the cell's file lists under ``scopes``; the scope that
brackets the hidden tree's reset and commit by the program's own constant,
which an older program lacks."""

from benchmark import harness
from handyrl_tpu.runtime import device_rollout


def read(run):
    return None


def work(run):
    """``flops``, ``bytes`` and ``tokens`` of one step, or None."""
    module = harness.load_module(run.path("flops", run.config["flops"] + ".py"))
    if not hasattr(module, "act_step"):
        return None
    return module.act_step(run.config, run.cell)


def program(run):
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    return shared.find(run, device_rollout, "STREAM_PROGRAM")


def commit_scope():
    return getattr(device_rollout, "COMMIT_SCOPE", None)


def scopes_share(run, names):
    """Percent of the rollout program's device seconds under ``names``
    together (none nests in another); None where the program, or every one of
    them, is missing.  Each goes to ``notes.rollout_scope_ms_per_step``."""
    found, rollout = program(run), [run.scope(name) if name else None for name in names]
    if found is None or not found["seconds"] or not any(rollout):
        return None
    steps = run.counters.get("k_steps")
    if found["whole_runs"] and steps:
        ms_per_step = 1e3 * found["whole_seconds"] / found["whole_runs"] / steps
        run.notes.setdefault("rollout_scope_ms_per_step", {}).update(
            {name: ms_per_step * inside["seconds"] / found["seconds"]
             for name, inside in zip(names, rollout) if inside})
    return 100.0 * sum(inside["seconds"] for inside in rollout if inside) / found["seconds"]
