"""JAX's persistent compilation cache, placed from outside.

Every entry point (main.py, chip_smoke.py, benchmark/run.py) calls
``enable_compile_cache()`` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and no path is
set in code; otherwise the cache lives at one fixed path inside the
checkout.  The path is part of the cache key, so it is never made from a
temp name, a pid or a time: a directory that moves never hits.

A process pinned to the CPU (``JAX_PLATFORMS=cpu``: tests, tool children)
gets no default path.  Reloaded CPU executables were checked to be
correct on jax 0.9.0 (tests/test_sentinel.py passes cold and warm), but
XLA:CPU's loader logs a multi-kilobyte machine-feature error on every
hit, which buries a child's real output.  Setting the variable turns the
cache on there too.
"""

from __future__ import annotations

import os
import threading
import zlib
from typing import Dict, Optional

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on and return its directory (None where
    it stays off, see above).  The thresholds drop to zero so the many
    small programs (inference buckets, rollout scans) are cached too, not
    only the big train step."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        if jax.config.jax_platforms == "cpu":
            return None
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def scoped_program_options(*scopes: str) -> Dict[str, int]:
    """``compiler_options`` for the ``jax.jit`` of a program that enters the
    ``jax.named_scope``s ``scopes`` for a profile's readers.

    jax keys the persistent cache by the computation with its locations
    stripped, and a scope lives in locations alone: a program that only
    gained or renamed a scope has the key it had, loads the executable
    compiled before, and shows that one's op names in every profile
    (PERF.md section 6, PR 39, met it on the chip;
    tests/test_program_phases.py shows it on a toy).  Compile options are
    part of the key, so the scopes' names go into one: the number of buffers
    XLA lists when asked to print a buffer assignment, which is read by no
    compiler pass.  A scope that moves without a new name keeps the key:
    clear the cache, or rename it."""
    digest = zlib.crc32(",".join(scopes).encode())
    return {"xla_debug_buffer_assignment_show_max": 16 + digest % 1_000_000}


class CompileCounters:
    """Process-wide compile accounting from jax's own monitoring events:
    persistent-cache hits and misses, and seconds spent tracing, lowering
    and compiling (or loading from the cache), summed over threads."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }
    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts = {"hits": 0, "misses": 0, "compile_s": 0.0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **kwargs) -> None:
        key = self._EVENTS.get(name)
        if key:
            with self._lock:
                self._counts[key] += 1

    def _on_duration(self, name: str, duration: float, **kwargs) -> None:
        if name in self._DURATIONS:
            with self._lock:
                self._counts["compile_s"] += float(duration)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counts)
