"""Runtime sanitizers: the dynamic half of the graftlint plane.

Static rules (tools/graftlint) catch what is visible in source; these two
context managers catch what is only visible at runtime, and are cheap
enough for tests and CI to arm around real training windows
(docs/static_analysis.md §Sanitizers):

* ``RecompileSentinel`` — counts REAL XLA compilations (jit cache
  misses) during a window, each attributed to the dispatch site that
  triggered it.  The streaming hot loop's contract is ZERO post-warm-up
  compiles per epoch: one stray shape change (a drifting batch geometry,
  an un-pinned sharding) silently turns a 3 ms update into a 30 s stall,
  which is exactly the class of regression a throughput assertion is too
  noisy to catch on CPU.
* ``HostSyncSanitizer`` — instruments the blocking-transfer entry points
  (``jax.block_until_ready``, ``jax.device_get``, and the
  ``ArrayImpl``-to-host conversions behind ``float()`` / ``.item()`` /
  ``np.asarray``) during a window and reports every hit as a NAMED site
  (file:line:function).  The ``batch_pipeline: device`` / device-replay
  hot paths must record ZERO: PR 6 removed the last per-dispatch host
  sync, and this is the harness that keeps it removed.

Both are nestable-free, thread-aware (events from rollout/pipeline
threads are attributed to their thread), and restore every patched entry
point on exit even when the body raises.
"""

from __future__ import annotations

import threading
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["RecompileSentinel", "HostSyncSanitizer", "SyncEvent", "CompileEvent"]


_JAX_PATH_MARKERS = ("/jax/", "/jaxlib/", "/jax_", "site-packages/jax")
_SELF_MARKERS = ("utils/sanitizers.py",)


def _attribute_site(skip_markers: Sequence[str]) -> Tuple[str, int, str]:
    """Deepest stack frame that is neither jax internals nor this module —
    the user-code site to blame.  Falls back to the deepest frame."""
    stack = traceback.extract_stack()
    for frame in reversed(stack):
        fn = frame.filename.replace("\\", "/")
        if any(m in fn for m in _JAX_PATH_MARKERS):
            continue
        if any(fn.endswith(m) or m in fn for m in _SELF_MARKERS):
            continue
        if any(m in fn for m in skip_markers):
            continue
        if fn.endswith(("threading.py", "contextlib.py")):
            continue
        return (fn, frame.lineno or 0, frame.name)
    last = stack[-1]
    return (last.filename, last.lineno or 0, last.name)


def _short(path: str, keep: int = 3) -> str:
    parts = path.replace("\\", "/").split("/")
    return "/".join(parts[-keep:])


# -- recompile sentinel -------------------------------------------------------


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class CompileEvent:
    site: Tuple[str, int, str]
    thread: str
    duration_s: float

    def format(self) -> str:
        f, line, func = self.site
        return f"{_short(f)}:{line} in {func}() [{self.thread}] ({self.duration_s:.3f}s)"


class RecompileSentinel:
    """Context manager asserting no XLA compilation happens in the window.

    Counts ``/jax/core/compile/backend_compile_duration`` monitoring
    events (one per REAL backend compile — jit cache hits emit nothing),
    attributing each to the dispatch site via the listener's synchronous
    stack.  Usage::

        with RecompileSentinel() as sentinel:
            ...run one epoch of the warm hot loop...
        sentinel.assert_no_recompiles("streaming epoch")

    The listener registry is process-global in jax; this class registers
    on ``__enter__`` and unregisters on ``__exit__`` (best effort — jax
    exposes removal as a private helper; when absent the listener stays
    registered but inert, gated by ``self._armed``).
    """

    def __init__(self) -> None:
        self.events: List[CompileEvent] = []
        self._armed = False
        self._lock = threading.Lock()

    # separate method so tests can exercise the listener directly
    def _on_event(self, name: str, duration: float, **kwargs: Any) -> None:
        if not self._armed or name != _COMPILE_EVENT:
            return
        event = CompileEvent(
            site=_attribute_site(()),
            thread=threading.current_thread().name,
            duration_s=float(duration),
        )
        with self._lock:
            self.events.append(event)

    def __enter__(self) -> "RecompileSentinel":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._armed = True
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax.monitoring

        self._armed = False
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    @property
    def count(self) -> int:
        return len(self.events)

    def report(self) -> str:
        if not self.events:
            return "RecompileSentinel: no compilations in window"
        lines = [f"RecompileSentinel: {len(self.events)} compilation(s) in window:"]
        lines += [f"  - {e.format()}" for e in self.events]
        return "\n".join(lines)

    def assert_no_recompiles(self, context: str = "") -> None:
        if self.events:
            prefix = f"[{context}] " if context else ""
            raise AssertionError(prefix + self.report())


# -- host-sync sanitizer ------------------------------------------------------


# sites where a blocking sync is the documented mechanism, not a leak:
# (path suffix fragment, function name) matched against the IMMEDIATE
# caller of the instrumented entry point
DEFAULT_ALLOWED_SITES: Tuple[Tuple[str, str], ...] = (
    # the CPU backend holds the dispatch locks until outputs are ready —
    # virtual devices share one thunk pool (parallel/mesh.py docstring)
    ("parallel/mesh.py", "dispatch_serialized"),
)


@dataclass
class SyncEvent:
    kind: str                       # block_until_ready | device_get | to_host
    site: Tuple[str, int, str]
    thread: str
    count: int = 1

    def format(self) -> str:
        f, line, func = self.site
        return f"{self.kind} at {_short(f)}:{line} in {func}() [{self.thread}] x{self.count}"


class HostSyncSanitizer:
    """Context manager counting blocking host<->device syncs by named site.

    Instruments, for the duration of the window:

    * ``jax.block_until_ready`` (module attribute — every repo call site
      spells it that way),
    * ``jax.device_get``,
    * ``ArrayImpl._value`` / ``ArrayImpl.__array__`` — the to-host
      conversion behind ``float(x)``, ``x.item()``, and ``np.asarray(x)``
      on device arrays (a single-device CPU array can short-circuit
      through the buffer protocol below Python; the device_get /
      block_until_ready hooks still see the repo's actual call sites).

    Re-entrant inner hits (device_get -> _value) count once.  Events
    whose immediate caller matches ``allow`` are recorded separately in
    ``allowed_events`` — visible in the report, excluded from
    ``assert_clean``.  Usage::

        with HostSyncSanitizer() as sync:
            ...pipeline window on the batch_pipeline: device path...
        sync.assert_clean("device pipeline window")
    """

    def __init__(self, allow: Sequence[Tuple[str, str]] = DEFAULT_ALLOWED_SITES):
        self.allow = tuple(allow)
        self.events: List[SyncEvent] = []
        self.allowed_events: List[SyncEvent] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._saved: List[Tuple[Any, str, Any]] = []

    # -- recording -----------------------------------------------------------

    def _record(self, kind: str) -> None:
        stack = traceback.extract_stack()
        # immediate caller = frame above the wrapper (wrapper is [-2])
        caller = stack[-3] if len(stack) >= 3 else stack[0]
        caller_file = caller.filename.replace("\\", "/")
        allowed = any(
            frag in caller_file and caller.name == func
            for frag, func in self.allow
        )
        site = _attribute_site(())
        event = SyncEvent(kind=kind, site=site,
                          thread=threading.current_thread().name)
        with self._lock:
            bucket = self.allowed_events if allowed else self.events
            for existing in bucket:
                if existing.kind == kind and existing.site == site:
                    existing.count += 1
                    return
            bucket.append(event)

    def _guarded(self, kind: str, orig: Callable) -> Callable:
        def wrapper(*args: Any, **kwargs: Any):
            if getattr(self._tls, "inside", False):
                return orig(*args, **kwargs)
            self._tls.inside = True
            try:
                self._record(kind)
                return orig(*args, **kwargs)
            finally:
                self._tls.inside = False

        wrapper.__name__ = getattr(orig, "__name__", kind)
        return wrapper

    # -- patching ------------------------------------------------------------

    def _patch(self, obj: Any, name: str, kind: str) -> None:
        orig = getattr(obj, name)
        self._saved.append((obj, name, orig))
        if isinstance(orig, property):
            fget = orig.fget
            guarded = self._guarded(kind, fget)
            setattr(obj, name, property(guarded, orig.fset, orig.fdel))
        else:
            setattr(obj, name, self._guarded(kind, orig))

    def __enter__(self) -> "HostSyncSanitizer":
        import jax

        self._patch(jax, "block_until_ready", "block_until_ready")
        self._patch(jax, "device_get", "device_get")
        from jax._src.array import ArrayImpl

        # _value is the cached to-host conversion float()/.item()/
        # __array__ funnel through (a property attached to the extension
        # type — patchable from Python)
        self._patch(ArrayImpl, "_value", "to_host")
        self._patch(ArrayImpl, "__array__", "to_host")
        return self

    def __exit__(self, *exc: Any) -> None:
        while self._saved:
            obj, name, orig = self._saved.pop()
            try:
                setattr(obj, name, orig)
            except Exception:
                pass

    # -- reporting -----------------------------------------------------------

    @property
    def count(self) -> int:
        return sum(e.count for e in self.events)

    def report(self) -> str:
        lines: List[str] = []
        if not self.events:
            lines.append("HostSyncSanitizer: no blocking host syncs in window")
        else:
            lines.append(
                f"HostSyncSanitizer: {self.count} blocking host sync(s) "
                f"at {len(self.events)} site(s):"
            )
            lines += [f"  - {e.format()}" for e in self.events]
        if self.allowed_events:
            lines.append(
                f"  (allowed: {sum(e.count for e in self.allowed_events)} "
                f"at {len(self.allowed_events)} allowlisted site(s))"
            )
        return "\n".join(lines)

    def assert_clean(self, context: str = "") -> None:
        if self.events:
            prefix = f"[{context}] " if context else ""
            raise AssertionError(prefix + self.report())
