"""Share of the train program's device time under the scope ``mla_core``:
a latent attention layer's scores (two products summed: a head's own key part
and the rotated part all heads share), mask, softmax and mix, forward and
backward: what a kernel for the core could take.  The whole mixer with its
projections is ``attn_step_share``.  Milliseconds a step go to the notes,
with ``mla_proj``'s."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("MLA_CORE_SCOPE")     # None: a program without the mixer
    share = None if scope is None else scopes.step_share(run, scope)
    if share is not None:
        run.notes["mla_ms_per_step"] = {
            name: shared.ms_per_step(run, shared.scope_name(constant))
            for name, constant in (("mla_core", "MLA_CORE_SCOPE"), ("mla_proj", "MLA_PROJ_SCOPE"))}
    return share
