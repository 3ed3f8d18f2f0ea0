"""The compressed convolutional attention's mixing's share of its roofline
(scope ``cca_mix``): the two convolutions' required operations and the
least bytes that pass through the mixing (``flops/zaya.py`` ``scope_work``),
whichever bounds, over the device time under the scope, forward and
backward.  Whatever implements the mixing reports through this."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("CCA_SCOPE")      # None: a program without the mixer
    return None if scope is None else scopes.roofline(run, scope)
