"""What every runner and every per-layer reader shares: finding a cell's
files by name, the device check, compile accounting, the profiler window,
the peak table, the reference comparison, the result line.

Nothing here knows a cell, a configuration or a metric by name: those live
in files of their own (see README.md) and are found through ``Run``.
"""

from __future__ import annotations

import importlib.util
import inspect
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# a line of these in the program's output means a fallback hid the real
# path (copied from chip_smoke.FALLBACK_MARKERS, which a later PR may move)
FALLBACK_MARKERS = (
    "falling back", "degrading", "device generation stops",
    "giving up on the rollout thread", "starting fresh", "Traceback",
)
# counters that must stay zero in a record the program writes
FALLBACK_COUNTERS = (
    "pipe_batcher_fallback", "plane_watchdog_stalls", "plane_watchdog_degraded",
    "serve_snapshot_substituted",
)
# the one limit on a run's life, counted from when its ``Run`` is made:
# run.py arms faulthandler with it, which ends a hung run inside the driver's
# 360 s with every thread's stack on stderr.  Every wait of the harness is
# derived from what is left of it (``Run.seconds_left``).
RUN_LIMIT_S = 330.0
# what a run still has to do once its profile is in hand or given up: the
# reference check, the readers, the two result lines
PROFILE_RESERVE_S = 20.0


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file of the benchmark by path (runners, readers,
    references and flops functions are found by name, not imported by
    the harness's source)."""
    name = "benchmark_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


class NoProgram(Exception):
    """The program in this checkout cannot run the cell: run.py prints the
    message and exits 3 with no result line."""


class Tee:
    """Program output goes to the run's log (searched for fallback
    markers afterwards) and to stderr, never to stdout: the last line of
    stdout is the result."""

    def __init__(self, sink):
        self.sink = sink

    def write(self, text):
        self.sink.write(text)
        return sys.__stderr__.write(text)

    def flush(self):
        self.sink.flush()
        sys.__stderr__.flush()

    def __getattr__(self, name):
        return getattr(sys.__stderr__, name)


class Run:
    """One run of one cell: what was asked, where its files are, and what
    the runner found.  Runners fill the result fields; readers read them."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, rehearse: bool, t_process: float):
        self.root = root
        self.workload = workload
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.t_process = t_process
        self.deadline = time.monotonic() + RUN_LIMIT_S
        spec_path = os.path.join(root, "BENCHMARK.json")
        if not os.path.exists(spec_path):
            spec_path = os.path.join(os.path.dirname(root), "BENCHMARK.json")
        self.spec = load_json(spec_path)
        self.cell = load_json(self.path("workloads", workload + ".json"))
        self.config = load_json(self.path("configs", self.cell["config"] + ".json"))
        self.chips = int(self.cell["chips"])
        # everything a run writes sits here, inside the checkout
        self.out_dir = os.path.join(REPO, "benchmark_out", workload)
        self.devices: List[Any] = []
        self.compile = None            # CompileCounters, set by run.py
        # -- filled by the runner --------------------------------------
        self.t_window = None           # monotonic start of the window
        self.window_s = 0.0
        self.values: Dict[str, float] = {}     # end-to-end values by name
        self.counters: Dict[str, float] = {}   # program counters over the window
        self.attempted = 0
        self.failed = 0
        self.checks: Dict[str, bool] = {}      # every one must hold for `correct`
        self.notes: Dict[str, Any] = {}        # printed on an earlier line
        # each number compared beside its limit: the result line's last key
        self.compared: Dict[str, List[float]] = {}
        self.setup_compile = None      # compile snapshot at window start
        self.end_compile = None        # compile snapshot at window end
        self.spans: List[Dict[str, Any]] = []  # trace.jsonl records in the window
        self.xplane: Optional[str] = None
        self.profile_session = None
        self.profile_t0 = 0.0
        self.live_bytes: List[int] = []   # bytes_in_use per chip when the window closed
        self.reduced: Optional[Dict[str, Any]] = None

    # -- files by name ---------------------------------------------------

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)

    def runner(self):
        return load_module(self.path("runners", self.cell["runner"] + ".py"))

    def reference(self):
        return load_module(self.path("reference", self.cell["config"] + ".py"))

    def required_work(self) -> Dict[str, float]:
        """Operations and bytes one update needs, from shapes: the
        function the configuration's file names under ``flops``."""
        module = load_module(self.path("flops", self.config["flops"] + ".py"))
        return module.train_update(self.config, self.cell)

    def scope_work(self) -> Optional[Dict[str, Dict[str, float]]]:
        """Operations and bytes one update needs inside each named scope
        (``scope_work(config, cell)`` of the same file: scope -> ``flops``,
        ``bytes``); None where the file has no such function."""
        module = load_module(self.path("flops", self.config["flops"] + ".py"))
        if not hasattr(module, "scope_work"):
            return None
        return module.scope_work(self.config, self.cell)

    def peaks(self) -> Dict[str, float]:
        table = load_json(os.path.join(HERE, "peaks.json"))
        # a rehearsal stands for the chip the cells are written for; its
        # numbers are never printed
        kind = "TPU v5 lite" if self.rehearse else self.devices[0].device_kind
        if kind not in table:
            raise KeyError(
                f"no peaks on record for device kind {kind!r}: add it to "
                "benchmark/peaks.json with its source"
            )
        return table[kind]

    def metric_names(self, group: str) -> List[str]:
        """The metrics of ``group`` (end_to_end / per_layer) this cell
        reports: those without a ``workloads`` list, or with it in it."""
        return [
            m["name"] for m in self.spec[group]
            if "workloads" not in m or self.workload in m["workloads"]
        ]

    def seconds_left(self) -> float:
        return self.deadline - time.monotonic()

    # -- what runners call -------------------------------------------------

    def require_module(self, module) -> None:
        """The net the program built is the one the configuration names
        under ``module``.  A program that lacks the configuration's net
        falls through to the environment's own (``envs/base.py``: any
        ``net`` it does not know) and would train that under the cell's
        name: called before a batch is made or a program compiled, this
        ends the run at once instead."""
        want, built = self.config["module"], type(module).__name__
        if built != want:
            raise NoProgram(
                f"configuration {self.config['name']} is run with the module {want}; "
                f"this checkout's program builds {built} for its env_args")

    def open_window(self) -> None:
        """Set-up is over: warm-up ran, every shape is compiled."""
        self.t_window = time.monotonic()
        self.setup_compile = self.compile.snapshot()
        self.values["setup_s"] = self.t_window - self.t_process

    def close_window(self, window_s: float, end_compile=None) -> None:
        self.window_s = float(window_s)
        self.end_compile = end_compile or self.compile.snapshot()
        self.live_bytes = [
            (d.memory_stats() or {}).get("bytes_in_use", 0) for d in self.devices]
        c0, c1 = self.setup_compile, self.end_compile
        compiled = (c1["hits"] + c1["misses"]) - (c0["hits"] + c0["misses"])
        self.counters["compiles_in_window"] = compiled
        self.counters["compile_s_in_window"] = c1["compile_s"] - c0["compile_s"]
        # a persistent-cache lookup is one per compiled program; where the
        # cache is off (CPU) only the traced seconds can tell
        self.checks["no_compile_in_window"] = (
            compiled == 0 and self.counters["compile_s_in_window"] < 0.05
        )

    def program(self, role: str) -> Optional[Dict[str, float]]:
        """``program_named`` the XLA module the cell's file names for
        ``role``; None where the trace cannot tell it from another role's
        program (same module name)."""
        names = self.cell.get("programs", {})
        module = names.get(role)
        if module is None or sum(1 for v in names.values() if v == module) > 1:
            return None
        return self.program_named(module)

    def program_named(self, module: str) -> Optional[Dict[str, float]]:
        """Device seconds and executions inside the traced window of the
        XLA program ``module`` (``jit_<fn>``, any fingerprint); None where
        the trace holds none.  ``seconds`` and ``runs`` hold a run the
        window's edge cuts by its part inside (for a share of the window),
        ``whole_seconds`` and ``whole_runs`` only the runs wholly inside
        (for a time per run)."""
        if self.reduced is None:
            return None
        hits = [v for k, v in self.reduced["programs"].items()
                if k.split("(")[0] == module]
        if not hits:
            return None
        return {key: sum(h[key] for h in hits) for key in hits[0]}

    def scope(self, name: str) -> Optional[Dict[str, float]]:
        """Device ``seconds`` (self time, averaged over the chips) and
        ``ops`` inside the traced window of the ops whose jax ``op_name``
        has ``name`` as a path component, forward and backward alike; None
        where the cell's file lists no ``scopes``, not this one, or no op
        carries it."""
        if self.reduced is None:
            return None
        return self.reduced.get("scopes", {}).get(name)


# ---------------------------------------------------------------------------
# the profiler window
# ---------------------------------------------------------------------------

WINDOW_BEGIN, WINDOW_END = "bench.window_begin", "bench.window_end"


def start_profile(run: Run) -> None:
    """Open a profiler session of this run's own.  Not ``jax.profiler.
    start_trace``: its ``stop_trace`` also converts the trace to JSON, which
    for the millions of op events of a few seconds of self-play takes
    minutes (this PR's first traced run of the loop cell never returned
    from it).  The session's ``stop()`` hands back the serialized planes."""
    import jax
    from jax._src.lib import _profiler      # the installed jax 0.9.0's session

    jax.devices()                           # the backend before the tracer
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # frames cost host time and say little
    options.host_tracer_level = 2
    run.profile_session = _profiler.ProfilerSession(options)
    with jax.profiler.TraceAnnotation(WINDOW_BEGIN):
        pass
    run.profile_t0 = time.monotonic()


def stop_profile(run: Run) -> None:
    import jax

    with jax.profiler.TraceAnnotation(WINDOW_END):
        pass
    run.notes["profile_window_s"] = time.monotonic() - run.profile_t0
    t0 = time.monotonic()
    session, run.profile_session = run.profile_session, None
    xspace = session.stop()
    os.makedirs(os.path.join(run.out_dir, "profile"), exist_ok=True)
    run.xplane = os.path.join(run.out_dir, "profile", "trace.xplane.pb")
    with open(run.xplane, "wb") as f:
        f.write(xspace)
    run.notes["profile_stop_s"] = time.monotonic() - t0
    run.notes["profile_bytes"] = len(xspace)


def join_profiler(run: Run, thread) -> None:
    """Wait for the thread that closes the traced window (a runner whose
    program has a loop of its own stops the profiler from a watcher thread)
    for as long as the run may live: ``ProfilerSession.stop()`` takes half a
    second a MB of profile, and a faster program writes more MB a second.
    A thread that still has not returned is said so, by name: ``run.xplane``
    stays unset, run.py's ``profile_collected`` check fails, and the note
    says how long the wait was and what had come back."""
    t0 = time.monotonic()
    thread.join(timeout=max(0.0, run.seconds_left() - PROFILE_RESERVE_S))
    if run.trace and thread.is_alive():
        run.notes["profile_not_collected"] = {
            "waited_s": time.monotonic() - t0,
            "seconds_left": run.seconds_left(),
            "stop_began": "profile_window_s" in run.notes,
            # stop() hands the planes over in one piece: none so far
            "profile_bytes": run.notes.get("profile_bytes", 0),
        }


def reduce_profile(run: Run) -> None:
    """The traced window is what lies between the two marker spans."""
    from benchmark import trace_reduce

    with open(run.xplane, "rb") as f:
        run.notes["profile_planes"] = trace_reduce.plane_sizes(f.read())
    # device time by named scope is a second pass over the op metadata,
    # made only for a cell that lists ``scopes``
    trace = trace_reduce.load_xplane(run.xplane, scopes=run.cell.get("scopes"))
    begin = [s for s in trace["host"] if s[0] == WINDOW_BEGIN]
    end = [s for s in trace["host"] if s[0] == WINDOW_END]
    window = (begin[0][2], end[-1][1]) if begin and end else None
    trace["host"] = [s for s in trace["host"] if s[0] not in (WINDOW_BEGIN, WINDOW_END)]
    run.reduced = trace_reduce.reduce_trace(trace, window)
    if "scopes" in run.reduced:
        run.notes["scopes"] = run.reduced["scopes"]


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def compare_outputs(system: Dict[str, Any], reference: Dict[str, Any],
                    tolerance: float, masks: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, float]:
    """Largest absolute difference per output head, over the entries
    ``masks[head]`` selects (all where absent), held to ``tolerance`` times
    the head's scale (its largest reference magnitude, at least 1).  The
    comparison that decides ``correct``: logits and values, never sampled
    moves."""
    import numpy as np

    out = {}
    ok = True
    for head, want in reference.items():
        got = np.asarray(system[head], np.float32)
        want = np.asarray(want, np.float32)
        diff = np.abs(got - want)
        if masks and head in masks:
            keep = np.broadcast_to(np.asarray(masks[head], bool), diff.shape)
            diff, want = diff[keep], want[keep]
        out[head] = float(diff.max()) if diff.size else math.inf
        scale = out[head + "_scale"] = float(np.abs(want).max()) if want.size else 0.0
        # a head of zeros would agree with anything of zeros: the sample
        # must carry signal for the check to mean something
        ok = ok and out[head] <= tolerance * max(1.0, scale) and scale > 5 * tolerance
    out["ok"] = ok
    return out


def limits(verdict: Dict[str, float], tolerance: float, prefix: str = "") -> Dict[str, List[float]]:
    """``compare_outputs``' verdict as name -> [number compared, its limit]."""
    return {
        prefix + head: [verdict[head], tolerance * max(1.0, verdict[head + "_scale"])]
        for head in verdict if head + "_scale" in verdict
    }


CHOICES = "choices"


def choices_agreement(system: Any, reference: Any, mask: Any = None) -> float:
    """Share of (row, step, layer) choice sets that are the same on both
    sides.  Each side is a pytree of integer arrays, one per routed layer,
    whose last axis holds the indices one token's result used; ``mask``
    (the leaves' shape without that axis, or with it as 1) selects the
    tokens that count."""
    import jax
    import numpy as np

    ours, theirs = jax.tree.leaves(system), jax.tree.leaves(reference)
    if len(ours) != len(theirs) or not ours:
        raise ValueError(
            f"choices: the system returned {len(ours)} routed layer(s), "
            f"the reference {len(theirs)}")
    same = counted = 0
    for a, b in zip(ours, theirs):
        a, b = np.sort(np.asarray(a), axis=-1), np.sort(np.asarray(b), axis=-1)
        if a.shape != b.shape or not np.issubdtype(a.dtype, np.integer):
            raise ValueError(f"choices: {a.dtype}{a.shape} against {b.dtype}{b.shape}")
        equal = (a == b).all(axis=-1)
        keep = np.ones(equal.shape, bool) if mask is None else np.broadcast_to(
            np.asarray(mask, bool).reshape(equal.shape), equal.shape)
        same += int((equal & keep).sum())
        counted += int(keep.sum())
    return same / counted if counted else 0.0


def judge_forward(system: Callable, reference_rows: Callable, params: Any, batch: Any,
                  config: Dict[str, Any], burn_in: int,
                  mask_of: Callable[[str], Any] = lambda head: None,
                  system_f32: Optional[Callable] = None):
    """The comparison that decides ``correct`` for a forward pass over
    training rows: ``system(params, batch)``, the timed path's forward in
    its compute dtype, against the configuration's plain reference
    ``reference_rows(params, batch, config, burn_in)`` in float32 under
    ``highest``.  ``mask_of(head)`` selects the entries of a head that
    count.  Returns (checks, notes, compared).

    A routed system returns, beside its heads, ``choices`` (see
    ``choices_agreement``), and its reference takes ``choices=None`` and
    returns its own under the same key.  A discrete choice cannot be held
    to a tolerance: rounding moves a token's k-th and (k+1)-th scores past
    each other and the two sides then add different experts.  So the
    reference runs twice.  *Forced*, with the system's choices, held to
    ``reference_tolerance``: ``matches_reference``, the arithmetic and its
    precision.  *Free*, with its own: the share of choice sets that agree
    is held to ``choices_agreement_floor``: ``choices_agree``, the routing.
    Where the configuration gives ``reference_tolerance_f32``,
    ``system_f32`` (the same forward, float32 parameters and compute, run
    under ``highest``) is held to it against the free reference:
    ``matches_reference_f32``, which one wrong expert fails.  ``choices``
    on one side only is an error."""
    import jax

    def reference(**given):
        with jax.default_matmul_precision("highest"):
            return dict(jax.device_get(jax.jit(
                lambda p, b, **kw: reference_rows(p, b, config, burn_in, **kw)
            )(params, batch, **given)))

    tolerance = float(config["reference_tolerance"])
    got = dict(jax.device_get(jax.jit(system)(params, batch)))
    chosen = got.pop(CHOICES, None)
    takes = CHOICES in inspect.signature(reference_rows).parameters
    if chosen is None and not takes:
        want = reference()
        verdict = compare_outputs(got, want, tolerance, _masks(mask_of, want))
        compared = limits(verdict, tolerance)
        return ({"matches_reference": verdict.pop("ok")},
                {"reference_max_abs_diff": verdict}, compared)
    if chosen is None:
        raise ValueError(
            f"{config['name']}: the reference takes choices, and the system's "
            "forward returned none")
    if not takes:
        raise ValueError(
            f"{config['name']}: the system's forward returned choices, and the "
            "reference's forward_rows takes none")

    forced, free = reference(choices=chosen), reference()
    if choices_agreement(forced.pop(CHOICES), chosen) != 1.0:
        raise ValueError(f"{config['name']}: the reference did not use the choices it was given")
    own = free.pop(CHOICES)
    masks = _masks(mask_of, free)
    verdict = compare_outputs(got, forced, tolerance, masks)
    floor = float(config["choices_agreement_floor"])
    agreement = choices_agreement(chosen, own, mask_of(CHOICES))
    compared = dict(limits(verdict, tolerance), choices_agreement=[agreement, floor])
    checks = {"matches_reference": verdict.pop("ok"), "choices_agree": agreement >= floor}
    notes = {
        "reference_max_abs_diff": verdict, "choices_agreement": agreement,
        # no check: what a plain comparison would have read
        "reference_free_max_abs_diff": compare_outputs(got, free, tolerance, masks),
    }
    if "reference_tolerance_f32" in config:
        if system_f32 is None:
            raise ValueError(
                f"{config['name']} gives reference_tolerance_f32, and the runner "
                "has no float32 forward")
        tolerance = float(config["reference_tolerance_f32"])
        with jax.default_matmul_precision("highest"):
            exact = dict(jax.device_get(jax.jit(system_f32)(params, batch)))
        exact.pop(CHOICES, None)
        verdict = compare_outputs(exact, free, tolerance, masks)
        compared.update(limits(verdict, tolerance, "f32_"))
        checks["matches_reference_f32"] = verdict.pop("ok")
        notes["reference_f32_max_abs_diff"] = verdict
    return checks, notes, compared


def _masks(mask_of: Callable[[str], Any], heads) -> Dict[str, Any]:
    return {head: mask for head in heads if (mask := mask_of(head)) is not None}


def log_has_fallback(log_path: str) -> List[str]:
    with open(log_path, errors="replace") as f:
        text = f.read()
    return [m for m in FALLBACK_MARKERS if m in text]


# ---------------------------------------------------------------------------
# the device and the result line
# ---------------------------------------------------------------------------


def device_record(run: Run) -> Dict[str, Any]:
    """The device as jax reports it.  ``memory_peak_bytes``, on the fullest
    chip: the allocator's ``peak_bytes_in_use``, or where that is larger
    the arrays alive when the window closed plus ``peak_bytes_reserved``.
    On this TPU runtime ``bytes_in_use`` counts arrays only; what loaded
    programs hold for their temporaries is ``bytes_reserved`` (the d1536
    step: 3.68 GB peak in use, exactly weights + optimizer + the set-up
    copy, and 4.27 GB reserved, the 4.3 GB ``memory_analysis()`` gives its
    program).  The two peaks need not coincide, so they are not added."""
    first = run.devices[0]
    stats = [(d.memory_stats() or {}) for d in run.devices]
    live = run.live_bytes or [0] * len(stats)
    per_chip = [
        max(s.get("peak_bytes_in_use", 0), alive + s.get("peak_bytes_reserved", 0))
        for s, alive in zip(stats, live)
    ]
    run.notes["memory"] = {
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "peak_bytes_reserved": [s.get("peak_bytes_reserved") for s in stats],
        "bytes_in_use_at_window_end": live,
        "bytes_limit": stats[0].get("bytes_limit"),
    }
    record = {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(run.devices), "memory_peak_bytes": int(max(per_chip)),
    }
    if run.reduced is not None:
        record["busy_s"] = run.reduced["busy_s"]
        record["window_s"] = run.reduced["window_s"]
    return record


def units(run: Run) -> Dict[str, str]:
    return {m["name"]: m["unit"] for g in ("end_to_end", "per_layer") for m in run.spec[g]}
