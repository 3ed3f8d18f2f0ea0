"""Share of the rollout program's device time spent on the per-row state:
under ``ssd`` (the Mamba-2 recurrence: every row's state read, decayed, added
to, read out and written) and ``state_commit`` (the rollout's own passes over
the whole hidden tree: zero where a lane starts again, keep the new state
where the player observed)."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "actor_step.py"))
    return shared.scopes_share(run, ("ssd", shared.commit_scope()))
