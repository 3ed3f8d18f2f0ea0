"""Share of the train program's device time under the scopes ``qk_norm`` (the
per-head RMSNorms of queries and keys, before the rotation) and ``attn_gate``
(the sigmoid of the gate's projection and its product with the core's output,
before ``o``), forward and backward.  Each goes to the notes in milliseconds
a step, with ``attn_proj``'s.

**What it sees.**  A scope's seconds are those of the fusions XLA *names*
after it, and a fusion is named by its root.  On the chip XLA fuses the
gate's sigmoid and product into the ``o`` product's operand, and their
cotangent into the gate's weight gradient, so that work reads under
``attn_proj`` and ``attn_gate`` reads next to nothing (0.005 ms a step of
``trinity_mini_train_t192``, PERF.md section 5, PR 58): while that holds the
metric is ``qk_norm``'s share alone, and the gate's element-wise time stands
in ``attn_proj_roofline``'s denominator without operations to answer for it.
An epilogue fused into the projections would therefore move ``attn_proj`` (the
notes' third figure), not this metric; what this metric could lose to a fused
epilogue is the norms' part."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    names = [shared.scope_name(constant) for constant in ("QK_NORM_SCOPE", "ATTN_GATE_SCOPE")]
    if None in names:       # a program without the scopes
        return None
    shares = [scopes.step_share(run, name) for name in names]
    if all(share is None for share in shares):
        return None
    run.notes["qk_gate_ms_per_step"] = {
        name: shared.ms_per_step(run, name)
        for name in names + [shared.scope_name("ATTN_PROJ_SCOPE")]}
    return sum(share or 0.0 for share in shares)
