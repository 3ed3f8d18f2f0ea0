"""``setup_s`` less every second that a compile record or a ``setup.*`` phase
covers (``setup_compile_wall_s`` says what they are; a second under both, or
under records of two threads, is covered once): the host's work before the
window that nothing names yet.  The interpreter's start, ``jax.devices()``,
the imports the package's own does not make, the benchmark's own traffic and
warm-up."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "setup_compile_wall_s.py"))
    found = shared.setup(run)
    if found is None:
        return None
    named = shared.spans_of(found["records"]) + shared.spans_of(found["phases"])
    return (found["hi"] - found["lo"]) - shared.union_s(found, named)
