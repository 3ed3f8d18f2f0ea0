"""Share of the train program's device time under the scope ``norm``: the
RMSNorm before every mixer and, in a sandwiched layer, the one after it, and
the final norm that closes every pass: float32 element-wise work over every
slot of the packed array, forward, replay and backward."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("NORM_SCOPE")
    return None if scope is None else scopes.step_share(run, scope)
