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
import sys
import threading
import time
import zlib
from typing import Any, Dict, List, Optional

import jax

from .trace import trace_event

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
    """Process-wide compile accounting from jax's own monitoring events.

    ``snapshot()``: persistent-cache hits and misses, and seconds spent
    tracing, lowering and compiling (or loading from the cache), summed over
    threads and over nesting levels (jax reports a trace for every jitted
    function it traces, the inner ones too, each inside the next).

    ``programs()``: one record for each of those events, on the clock the
    tracer's spans are on (``time.monotonic()``):

    ========== =============================================================
    key        holds
    ========== =============================================================
    program    ``fun_name`` as jax gives it: ``_step`` for a trace,
               ``jit(_step)`` for the lowering and the backend
    phase      ``trace``, ``lower`` or ``backend``
    t_mono     the start (jax hands wall times: converted once, here)
    dur_s      the length
    thread     the compiling thread's name
    nested     a ``trace`` record inside another ``trace`` record of its
               thread: its seconds are the outer record's too, so a reader
               that counts each second once leaves it out
    cache      ``backend`` only: ``hit`` or ``miss``, from the cache events
               that fired on the thread while the record was open; None
               where the persistent cache is off or wrote no entry
    retrieval_s, saved_s
               on a hit: the seconds the read took, and the compile seconds
               the entry says it saved
    ========== =============================================================

    At most ``MAX_RECORDS`` are kept (further ones counted in ``dropped``);
    nested traces under ``FOLD_UNDER_S`` (the ``jnp`` one-liners inside a
    traced body) are folded into one record a thread (``folded``: how many).
    While a tracer is on, each ``backend`` record, and each ``trace`` or
    ``lower`` record of ``EVENT_MIN_S`` or more that is not nested, is also
    written with ``trace_event`` at its own start: ``compile.build`` (a
    miss, or no cache), ``compile.load`` (a hit), ``compile.trace``,
    ``compile.lower``.  A build of ``report_build_s`` seconds or more is
    said once on stderr, by name: a run that a watchdog ends still leaves
    the name of what ate its budget."""

    _EVENTS = {     # event -> (the total it counts into, a backend record's ``cache``)
        "/jax/compilation_cache/cache_hits": ("hits", "hit"),
        "/jax/compilation_cache/cache_misses": ("misses", "miss"),
    }
    _TRACE = "/jax/core/compile/jaxpr_trace_duration"
    _PHASES = {
        _TRACE: "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
    }
    _CACHE_SECONDS = {
        "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
        "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
    }
    MAX_RECORDS = 4096
    FOLD_UNDER_S = 1e-3
    EVENT_MIN_S = 0.010
    FOLDED = "<nested traces under 1 ms>"

    def __init__(self, report_build_s: float = 5.0) -> None:
        self.report_build_s = float(report_build_s)
        self.dropped = 0
        self._lock = threading.Lock()
        self._counts = {"hits": 0, "misses": 0, "compile_s": 0.0}
        self._records: List[Dict[str, Any]] = []
        self._folded: Dict[str, Dict[str, Any]] = {}
        # what the compiling thread has seen since its last backend record
        # closed (``cache``, the two durations) and how many traces it has open
        self._thread = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_cache_seconds)
        jax.monitoring.register_scalar_listener(self._on_start)
        jax.monitoring.register_event_time_span_listener(self._on_span)
        self._listening = True

    def _on_event(self, name: str, **kwargs) -> None:
        if name in self._EVENTS:
            key, self._thread.cache = self._EVENTS[name]
            with self._lock:
                self._counts[key] += 1

    def _on_cache_seconds(self, name: str, duration: float, **kwargs) -> None:
        key = self._CACHE_SECONDS.get(name)
        if key:
            setattr(self._thread, key, float(duration))

    def _on_start(self, name: str, value: float, **kwargs) -> None:
        # jax says when a timed section opens, too: the traces this thread has
        # open tell a nested one when it closes
        if name == self._TRACE:
            self._thread.open_traces = getattr(self._thread, "open_traces", 0) + 1

    def _on_span(self, name: str, start: float, end: float, fun_name: str = "?",
                 **kwargs) -> None:
        phase = self._PHASES.get(name)
        if phase is None:
            return
        dur = end - start
        local = self._thread
        record: Dict[str, Any] = {
            "program": str(fun_name), "phase": phase,
            # the spans' clock: every trace.jsonl record is on it
            "t_mono": time.monotonic() - (time.time() - start), "dur_s": dur,
            "thread": threading.current_thread().name, "nested": False,
        }
        if phase == "trace":
            local.open_traces = max(getattr(local, "open_traces", 1) - 1, 0)
            record["nested"] = local.open_traces > 0
        elif phase == "backend":
            hit = getattr(local, "cache", None) == "hit"
            record.update(
                cache=getattr(local, "cache", None),
                retrieval_s=getattr(local, "retrieval_s", None) if hit else None,
                saved_s=getattr(local, "saved_s", None) if hit else None)
            local.cache = local.retrieval_s = local.saved_s = None
        with self._lock:
            self._counts["compile_s"] += float(dur)
            if record["nested"] and dur < self.FOLD_UNDER_S:
                folded = self._folded.setdefault(record["thread"], dict(
                    record, program=self.FOLDED, dur_s=0.0, folded=0))
                folded["dur_s"] += dur
                folded["folded"] += 1
                return
            if len(self._records) >= self.MAX_RECORDS:
                self.dropped += 1
            else:
                self._records.append(record)
        if not record["nested"]:
            self._tell(record)

    def _tell(self, record: Dict[str, Any]) -> None:
        """The record as a ``compile.*`` event (a no-op while no tracer is
        on), and a slow build as a line on stderr."""
        program, phase, dur = record["program"], record["phase"], record["dur_s"]
        if phase != "backend":
            if dur >= self.EVENT_MIN_S:
                trace_event("compile." + phase, dur, record["t_mono"], program=program)
            return
        cache = record["cache"]
        trace_event("compile.load" if cache == "hit" else "compile.build", dur,
                    record["t_mono"], program=program, cache=cache)
        if cache != "hit" and dur >= self.report_build_s:
            why = ("no entry in the compile cache" if cache == "miss"
                   else "the compile cache is off or wrote no entry")
            print(f"[handyrl_tpu] built {program} in {dur:.1f} s ({why})",
                  file=sys.stderr, flush=True)

    def close(self) -> None:
        """Stop listening (jax keeps a listener until it is taken off: a
        process that makes counters again and again, as tests do, closes
        them).  What was recorded stays readable; a second call does nothing."""
        if not self._listening:
            return
        self._listening = False
        monitoring = jax.monitoring
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_cache_seconds)
        monitoring.unregister_scalar_listener(self._on_start)
        monitoring.unregister_event_time_span_listener(self._on_span)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counts)

    def programs(self) -> List[Dict[str, Any]]:
        """A copy of the records, in the order their events closed (an inner
        trace before the one round it), the folded remainders last."""
        with self._lock:
            return [dict(r) for r in self._records + list(self._folded.values())]
