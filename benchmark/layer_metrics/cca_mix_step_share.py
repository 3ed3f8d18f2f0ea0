"""Share of the train program's device time under the scope ``cca_mix``:
what a compressed convolutional attention layer does to queries, keys and
values between its projections and the rotation (the value shift, both
convolutions, the q-k mean, the L2 norms, the temperature), forward and
backward.  Milliseconds a step go to the notes."""

from benchmark import harness, scopes


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "dense_trunk.py"))
    scope = shared.scope_name("CCA_SCOPE")      # None: a program without the mixer
    share = None if scope is None else scopes.step_share(run, scope)
    if share is not None:
        run.notes["cca_mix_ms_per_step"] = shared.ms_per_step(run, scope)
    return share
