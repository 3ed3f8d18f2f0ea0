"""Share of the rollout program's device time under the expert sub-layers'
scopes: ``route`` (scores, choice, sorting rows into the buffer and back),
``experts`` (the grouped products over the row buffer) and ``shared_expert``."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "actor_step.py"))
    return shared.scopes_share(run, ("route", "experts", "shared_expert"))
