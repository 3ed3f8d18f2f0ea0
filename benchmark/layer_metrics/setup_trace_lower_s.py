"""Seconds of set-up in which at least one thread was tracing or lowering a
program: the union of the ``trace`` and ``lower`` compile records before the
window (``setup_compile_wall_s`` says what a record is).  jax's Python-side
work, which no compile cache saves and which grows with the layers a program
unrolls and with its kernels' bodies."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "setup_compile_wall_s.py"))
    found = shared.setup(run)
    if found is None:
        return None
    return shared.union_s(found, shared.spans_of(
        found["records"], lambda r: r["phase"] in ("trace", "lower")))
