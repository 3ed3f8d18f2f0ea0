"""Share of the train program's device time under the scope ``attn``: the
q, k, v and o projections, the rotations (``rope``) and the scores, softmax
and mix (``gqa``) of every attention application.  ``rope`` and ``gqa`` go
to the notes in milliseconds a step."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("ATTN_SCOPE")
    share = None if scope is None else scopes.step_share(run, scope)
    if share is not None:
        run.notes["attn_ms_per_step"] = {
            name: shared.ms_per_step(run, shared.scope_name(constant))
            for name, constant in (("attn", "ATTN_SCOPE"), ("rope", "ROPE_SCOPE"),
                                   ("gqa", "GQA_SCOPE"))}
    return share
