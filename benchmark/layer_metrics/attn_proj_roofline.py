"""A gated attention layer's projections' share of their roofline (scope
``attn_proj``: q, k, v, the gate's and o, of the local and the global layers
alike): their required operations and the least bytes that pass through them
(``flops/afmoe.py`` ``scope_work``), whichever bounds, over the device time
under the scope, forward and backward.  The time holds what XLA fuses
into those products beside them: the gate's sigmoid and product ride in the
``o`` product's fusion (``qk_gate_step_share.py``), element-wise work the count
of operations does not hold."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("ATTN_PROJ_SCOPE")    # None: a program without the scope
    return None if scope is None else scopes.roofline(run, scope)
