"""A latent attention layer's core's share of its roofline (scope
``mla_core``): the scores' and the mix's required operations and the least
bytes that pass through them (``flops/kanana.py`` ``scope_work``), whichever
bounds, over the device time under the scope, forward and backward.  Whatever
implements the core, in either form, reports through this."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("MLA_CORE_SCOPE")     # None: a program without the mixer
    return None if scope is None else scopes.roofline(run, scope)
