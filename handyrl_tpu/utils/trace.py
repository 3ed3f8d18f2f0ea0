"""Structured hot-path span tracing: the run-wide observability plane.

Five planes (shm batchers, split actor/learner meshes, multi-host cadence,
serving, league) each report per-epoch COUNTERS into metrics.jsonl, but
counters cannot say *where time goes inside an epoch* — which plane is the
bottleneck on real chips is exactly the question the Podracer/Sebulba
disaggregated design keeps asking.  This module answers it with spans::

    from handyrl_tpu.utils.trace import trace_span

    with trace_span("train_step", plane="learner"):
        state, metrics = ctx.train_step(state, batch, lr)

Design constraints, in order:

1. **Off by default and provably free.**  ``trace_span`` with tracing
   disabled returns one shared no-op context manager — a single module
   attribute check, no allocation, no jax import, no syscalls.  The hot
   path is bit-identical with ``trace: false`` and the sanitizer suite
   pins zero added host syncs / recompiles (tests/test_trace.py).
2. **Lock-cheap, never blocking.**  Enabled spans append one small dict
   to a bounded in-process ring under a lock held for the append only; a
   full ring DROPS the span and counts it (``dropped``) — tracing load
   must never stall a dispatch.  A background flusher drains the ring to
   ``trace.jsonl``.
3. **Crash-tolerant output.**  One JSON line per span, batches written in
   a single ``write`` + flush (+ best-effort fsync), so a SIGKILL leaves
   at most one truncated FINAL line — the same tail discipline as
   metrics.jsonl, tolerated by ``read_trace`` exactly like
   ``utils.metrics.read_metrics``.
4. **Device-profile correlation.**  Each span also enters a
   ``jax.profiler.TraceAnnotation`` (when jax is importable and
   ``trace.annotate_device`` is true), so the host-side spans land inside
   XLA device profiles captured with ``profile_dir``.

Set-up has spans of its own, ``trace_phase``: for work that runs a bounded
number of times a process, recorded whether or not a tracer is on (most
entry points configure theirs after set-up is over) and written to the
sink once there is one.  Its contract is in its docstring.

``scripts/trace_export.py`` converts one or more trace.jsonl files (one
per rank in a multi-process run) into Chrome trace-event JSON that opens
directly in ``chrome://tracing`` / Perfetto.  Span catalog and workflow:
docs/observability.md.
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "Tracer",
    "configure",
    "shutdown",
    "enabled",
    "trace_span",
    "trace_event",
    "trace_phase",
    "trace_phase_since",
    "phases",
    "trace_stats",
    "read_trace",
    "META_NAME",
]

TRACE_SCHEMA_VERSION = 1
# the first line of every trace.jsonl: wall-clock <-> monotonic anchor so
# the exporter can align ranks whose monotonic epochs differ (each process
# — and each HOST — has its own)
META_NAME = "__trace_meta__"


class _NullSpan:
    """The disabled-path context manager: one shared instance, no state."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """An enabled ``trace_span``, or a ``trace_phase`` (``phase``: recorded
    whether or not the tracer is on)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_phase", "_ts", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Optional[Dict[str, Any]],
                 phase: bool = False):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._phase = phase
        self._ann = None

    def __enter__(self) -> "_Span":
        tracer = self._tracer
        ann_cls = tracer._annotation if tracer.enabled else None
        if ann_cls is not None:
            # enter the XLA annotation FIRST so the device profile's span
            # brackets the same wall window the host span records
            try:
                ann = ann_cls(self._name)
                ann.__enter__()
                self._ann = ann
            except Exception:
                tracer._annotation = None  # mis-matched jax: disarm once
        self._ts = time.time()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc: Any) -> bool:
        dur = time.monotonic() - self._t0
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        self._tracer._record(self._name, self._ts, self._t0, dur, self._attrs, self._phase)
        return False


class Tracer:
    """In-process span recorder behind the module-level ``trace_span``.

    One instance per process (the module singleton); ``configure`` is
    called once by the entry points (Learner, ServingServer, tests) with
    ``train_args.trace``.  All public state is documented: ``spans`` /
    ``dropped`` are cumulative counters surfaced as ``trace_*`` metrics.
    """

    def __init__(self) -> None:
        self.enabled = False
        self.path: Optional[str] = None
        self.ring_size = 4096
        self.flush_interval = 0.5
        self.rank = 0
        self.spans = 0
        self.dropped = 0
        # once-a-process phases (``trace_phase``): kept whether or not the
        # tracer is configured, so that set-up has spans before a sink exists
        self.max_phases = 64
        self.phases: List[Dict[str, Any]] = []
        self.phases_dropped = 0
        self._annotation = None      # jax.profiler.TraceAnnotation when armed
        self._ring: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._flusher: Optional[threading.Thread] = None
        self._file = None
        self._atexit_registered = False

    # -- lifecycle -----------------------------------------------------------

    def configure(self, cfg: Optional[Dict[str, Any]], rank: int = 0) -> bool:
        """Arm (or disarm) tracing from a ``train_args.trace`` dict.

        Returns True when tracing came up enabled.  Raises ``ValueError``
        naming the knob when the trace path is not writable — a run asked
        to trace must fail at startup, not silently record nothing.  In a
        multi-process run every rank writes its OWN file: rank N > 0
        derives ``trace.jsonl`` -> ``trace.rankN.jsonl``.
        """
        self.shutdown()  # re-configuration replaces the previous plane
        self.spans = 0
        self.dropped = 0
        cfg = dict(cfg or {})
        if not cfg.get("enabled"):
            return False
        path = str(cfg.get("path") or "trace.jsonl")
        rank = int(rank)
        if rank > 0:
            root, ext = os.path.splitext(path)
            path = f"{root}.rank{rank}{ext or '.jsonl'}"
        try:
            f = open(path, "a")
        except OSError as exc:
            raise ValueError(
                f"train_args.trace.path={path!r} is not writable "
                f"({type(exc).__name__}: {exc}) — tracing was requested, so "
                "an unwritable sink is a startup error, not a silent no-op"
            ) from exc
        self._file = f
        self.path = path
        self.rank = rank
        self.ring_size = max(1, int(cfg.get("ring_size", 4096)))
        self.flush_interval = max(0.01, float(cfg.get("flush_interval", 0.5)))
        self._annotation = None
        if cfg.get("annotate_device", True):
            try:
                import jax.profiler

                self._annotation = jax.profiler.TraceAnnotation
            except Exception:
                self._annotation = None  # jax-free process: host spans only
        # the wall<->monotonic anchor rides the file, not the ring: it must
        # be the first line even if the ring later overflows
        meta = {
            "name": META_NAME,
            "version": TRACE_SCHEMA_VERSION,
            "ts": time.time(),
            "t_mono": time.monotonic(),
            "rank": self.rank,
            "pid": os.getpid(),
        }
        # right behind it, the phases the process recorded before it had a
        # sink: set-up is over by the time most entry points configure
        with self._lock:
            earlier = list(self.phases)
        f.write("".join(json.dumps(r, default=float) + "\n" for r in [meta] + earlier))
        f.flush()
        self._stop = threading.Event()
        self.enabled = True
        self._flusher = threading.Thread(
            target=self._flush_loop, daemon=True, name="trace-flusher"
        )
        self._flusher.start()
        if not self._atexit_registered:
            self._atexit_registered = True
            atexit.register(self.shutdown)
        return True

    def shutdown(self) -> None:
        """Disarm and drain: stop the flusher, flush the ring tail, close
        the file.  Safe to call repeatedly (atexit + explicit callers)."""
        if not self.enabled and self._file is None:
            return
        self.enabled = False
        self._stop.set()
        flusher, self._flusher = self._flusher, None
        if flusher is not None and flusher is not threading.current_thread():
            flusher.join(timeout=2.0)
        self.flush()
        f, self._file = self._file, None
        if f is not None:
            try:
                f.close()
            except OSError:
                pass

    # -- recording -----------------------------------------------------------

    def _record(self, name: str, ts: float, t0: float, dur: float,
                attrs: Optional[Dict[str, Any]], phase: bool = False) -> None:
        rec: Dict[str, Any] = {
            "name": name,
            "ts": round(ts, 6),
            "t_mono": round(t0, 6),
            "dur_s": round(dur, 9),
            "thread": threading.current_thread().name,
            "rank": self.rank,
        }
        if attrs:
            rec["attrs"] = attrs
        with self._lock:
            if phase:
                # kept for the process's life, tracer or no tracer; the ring
                # takes it too while there is a sink to drain it into
                rec["phase"] = True
                if len(self.phases) >= self.max_phases:
                    self.phases_dropped += 1
                else:
                    self.phases.append(rec)
                if not self.enabled:
                    return
            if len(self._ring) >= self.ring_size:
                # NEVER block a hot path on the flusher: drop + count
                self.dropped += 1
                return
            self._ring.append(rec)
            self.spans += 1

    def flush(self) -> None:
        """Drain the ring to disk: one write() for the whole batch (a kill
        mid-write truncates only the final line — the metrics.jsonl tail
        discipline), flushed, fsync best-effort."""
        with self._lock:
            if not self._ring:
                return
            batch, self._ring = self._ring, []
        f = self._file
        if f is None:
            return
        try:
            f.write("".join(json.dumps(r, default=float) + "\n" for r in batch))
            f.flush()
            try:
                os.fsync(f.fileno())
            except OSError:
                pass
        except (OSError, ValueError):
            pass  # a torn-down sink must not kill the instrumented thread

    def _flush_loop(self) -> None:
        while not self._stop.wait(self.flush_interval):
            self.flush()


_TRACER = Tracer()


def configure(cfg: Optional[Dict[str, Any]], rank: int = 0) -> bool:
    return _TRACER.configure(cfg, rank)


def shutdown() -> None:
    _TRACER.shutdown()


def enabled() -> bool:
    return _TRACER.enabled


def current_path() -> Optional[str]:
    """The armed tracer's sink path (rank suffix applied), or None."""
    return _TRACER.path if _TRACER.enabled else None


def trace_span(name: str, **attrs: Any):
    """Span context manager around a hot-path section.

    Disabled (the default): returns the shared no-op instance — the whole
    cost is this attribute check.  Enabled: records name, wall + monotonic
    start, duration, thread and rank into the ring, and brackets the body
    in a ``jax.profiler.TraceAnnotation`` so it shows inside XLA device
    profiles.  Keyword attrs must be cheap constants (they are evaluated
    at the call site either way)."""
    if not _TRACER.enabled:
        return _NULL_SPAN
    return _Span(_TRACER, name, attrs or None)


def trace_event(name: str, dur_s: float, t0: Optional[float] = None,
                **attrs: Any) -> None:
    """Record an already-measured duration as a span (for seams that time
    themselves anyway, and for async lifecycles like a serving request
    where enter/exit happen on different threads).  ``t0`` is the span's
    start on ``time.monotonic()``; omitted, it is derived as now - dur."""
    tracer = _TRACER
    if not tracer.enabled:
        return
    now = time.monotonic()
    start = now - dur_s if t0 is None else t0
    tracer._record(name, time.time() - (now - start), start, dur_s, attrs or None)


def trace_phase(name: str, **attrs: Any):
    """Span context manager for work that runs a bounded number of times a
    process: set-up (the package's import, building a net, a context, a
    state, a program's first call, a learner, an actor's weights).

    Unlike ``trace_span`` it records whether or not a tracer is on: into a
    list the tracer keeps for the process's life (``phases()``; 64 records,
    further ones dropped and counted), as a span's record with ``"phase":
    true``.  ``configure()`` writes the phases recorded before it right
    behind the meta line; one recorded while a tracer is on also goes
    through the ring, and brackets its body in a ``TraceAnnotation``.

    The contract, which tests/test_trace.py holds: **no function that a
    window's loop calls enters one.**  N updates, N dispatches, N epochs
    leave the count of phases where it was.  Anything that repeats takes
    ``trace_span``, whose off path is free."""
    return _Span(_TRACER, name, attrs or None, phase=True)


def trace_phase_since(name: str, t0: float, **attrs: Any) -> None:
    """Record as a phase (see ``trace_phase``) what began at ``t0`` on
    ``time.monotonic()`` and ends now: for a site that learns only afterwards
    that it was a phase (a step's first call for a program), or that cannot
    take a ``with`` (a module's import, first line to last)."""
    now = time.monotonic()
    _TRACER._record(name, time.time() - (now - t0), t0, now - t0, attrs or None, phase=True)


def phases() -> List[Dict[str, Any]]:
    """A copy of the phases this process has recorded so far."""
    with _TRACER._lock:
        return [dict(r) for r in _TRACER.phases]


def trace_stats() -> Dict[str, int]:
    """Cumulative tracer health counters (the ``trace_*`` metrics keys)."""
    return {"trace_spans": _TRACER.spans, "trace_dropped": _TRACER.dropped}


def read_trace(path: str, strict: bool = False) -> List[Dict[str, Any]]:
    """Parse a trace.jsonl, tolerating exactly one truncated FINAL line
    (the write a kill can interrupt) unless ``strict``; invalid JSON on
    any earlier line raises — mid-file corruption is a real integrity
    problem, not an artifact of the append protocol."""
    with open(path) as f:
        lines = f.readlines()
    records: List[Dict[str, Any]] = []
    last = len(lines) - 1
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            if i == last and not strict:
                print(
                    f"[handyrl_tpu] {path}: dropping truncated final trace "
                    "line (half-written record from a killed run)",
                    file=sys.stderr,
                )
                break
            raise
    return records
