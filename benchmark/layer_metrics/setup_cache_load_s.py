"""Seconds of set-up in which at least one thread was reading an executable
from the persistent compile cache and loading it: the union of the ``backend``
compile records before the window that hit the cache (``setup_compile_wall_s``
says what a record is).  It goes with the entries' size (PERF.md section 6,
PR 55, has what a MB costs on the v5e's host).  None where no record says hit
or miss: the cache is off (a CPU rehearsal without
``JAX_COMPILATION_CACHE_DIR``)."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "setup_compile_wall_s.py"))
    found = shared.setup(run)
    if found is None or not any(r.get("cache") for r in found["records"]):
        return None
    return shared.union_s(found, shared.spans_of(
        found["records"], lambda r: r.get("cache") == "hit"))
