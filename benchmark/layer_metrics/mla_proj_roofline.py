"""A latent attention layer's projections' share of their roofline (scope
``mla_proj``: q, the map to the latent and the shared key part with the
latent's norm, the map from the latent to every head's key part and value,
o): their required operations and the least bytes that pass through them
(``flops/kanana.py`` ``scope_work``), whichever bounds, over the device time
under the scope, forward and backward."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("MLA_PROJ_SCOPE")     # None: a program without the mixer
    return None if scope is None else scopes.roofline(run, scope)
