"""The gated MLPs' share of their roofline: the three products of every
``-`` application a pass (``flops/<family>.py`` ``scope_work``, scope
``mlp``) over the device time under that scope, forward, replay and
backward.  The program runs them over the packed array's padding and once
more for each checkpoint it replays; the count holds tokens and no replay."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("MLP_SCOPE")
    return None if scope is None else scopes.roofline(run, scope)
