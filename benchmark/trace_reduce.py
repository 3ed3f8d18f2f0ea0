"""From a profiler trace to numbers: device busy and idle time, time per
XLA program, collective time exposed or hidden, idle gaps by host span.

The yardstick's reducer.  It reads what ``jax.profiler`` writes
(``*.xplane.pb``) through ``jax.profiler.ProfileData`` and nothing else.
Two steps, so the arithmetic can be tested without a profiler:

    trace = load_xplane(path)        # planes -> plain lists of intervals
    reduced = reduce_trace(trace)    # intervals -> seconds and shares

What a v5e trace holds (looked at by hand, docs/captures/bf16_profile_*):
a plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per execution of a jitted program, named ``jit_<fn>(<fingerprint>)``),
``XLA Ops`` (every HLO op, nested: a ``%while`` spans its body's ops) and
``Async XLA Ops`` (``*-start`` to ``*-done`` intervals: copies, async
collectives); and ``/host:CPU`` with a line per thread, where
``jax.profiler.TraceAnnotation`` spans (the program's ``trace_span``) sit
on the same clock as the device events.

Definitions (seconds, inside the window):

* busy      union of the ``XLA Ops`` intervals of a chip (``XLA Modules``
            where a trace has no op line); idle = window - busy.
* program   busy time lying inside that program's ``XLA Modules`` events,
            so program times (with ``no_program`` for ops outside any
            module) sum to busy.  ``seconds`` and ``runs`` count a run the
            window's edge cuts by the part inside, as one run: right for a
            share of the window.  ``whole_seconds`` and ``whole_runs`` count
            only runs that lie wholly inside: right for a time per run.
* collective  union of the collective ops' intervals (sync ops on the op
            line, ``-start``..``-done`` on the async line); exposed = the
            part of it during which no other leaf op runs on that chip.
* gap       a maximal idle interval; named by the innermost host span
            open at its middle, or ``no_span``.
* scope     only where the trace lists ``scopes``: the self seconds and the
            number of the ops (as the op table counts them: an op the
            window's edge cuts counts whole) whose jax ``op_name`` has the
            scope's name as a path component, forward and backward
            (``transpose(jvp(..))``) alike.  A fusion carries one
            ``op_name``, its root's: what XLA fused across a scope's edge
            goes to one side.  Nested scopes each count their ops, so
            scopes sum to the program's seconds at most where none lies
            inside another.

Where several chips are traced, seconds are averaged over them; gaps and
the op table come from the first chip.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[str, float, float]            # name, start_s, end_s
HostSpan = Tuple[str, float, float, str]       # name, start_s, end_s, thread

DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)(-start|-done)?\b"
)
# the program's span names: lowercase dotted identifiers (train_step,
# dispatch.run, epoch.snapshot_wait, bench.block); python-tracer frames
# start with '$' and jax's own TraceMes carry '(' or '::'
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*$")
NO_SPAN = "no_span"
NO_PROGRAM = "no_program"
PROGRAM_KEYS = ("seconds", "runs", "whole_seconds", "whole_runs")
# gaps shorter than this are summed under one name instead of being
# looked up one by one (a launch-bound loop has hundreds of thousands)
SHORT_GAP_S = 20e-6


def load_xplane(path: str, scopes: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """Read one ``.xplane.pb`` into plain lists.  Times are seconds on the
    trace's own clock.  Host events are kept where their name is a span's
    (``SPAN_NAME``).  With ``scopes`` each device also gets ``op_names``,
    the jax ``op_name`` of each event of ``ops`` (``op_names_by_event``),
    and the trace the list under ``scopes``; without, nothing more is read
    than before."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    named: Dict[str, Dict[str, str]] = {}
    if scopes:
        with open(path, "rb") as f:
            named = op_names_by_event(f.read())
    devices: Dict[int, Dict[str, List[Interval]]] = {}
    host: List[HostSpan] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines: Dict[str, List[Interval]] = {}
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops", "Async XLA Ops"):
                    lines[line.name] = [
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    ]
            if lines:
                device = devices[int(m.group(1))] = {
                    "modules": lines.get("XLA Modules", []),
                    "ops": lines.get("XLA Ops", []),
                    "async_ops": lines.get("Async XLA Ops", []),
                }
                if scopes:
                    by_event = named.get(plane.name, {})
                    device["op_names"] = [by_event.get(name, "") for name, _, _ in device["ops"]]
        elif plane.name.startswith("/host:") and plane.name != "/host:metadata":
            for line in plane.lines:
                for e in line.events:
                    if SPAN_NAME.match(e.name):
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9, line.name))
    trace = {"devices": devices, "host": host}
    if scopes:
        trace["scopes"] = list(scopes)
    return trace


def _varint(buf: memoryview, pos: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, pos


def _fields(buf: memoryview) -> Iterator[Tuple[int, memoryview]]:
    """(field number, payload) of each length-delimited field of one
    protobuf message; scalar fields are stepped over."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 2:
            size, pos = _varint(buf, pos)
            yield key >> 3, buf[pos:pos + size]
            pos += size
        elif kind == 0:
            _, pos = _varint(buf, pos)
        else:
            pos += 8 if kind == 1 else 4


def plane_sizes(raw: bytes, lines_kept: int = 4) -> Dict[str, Dict[str, Any]]:
    """What a serialized profile's bytes are made of: for each plane its
    bytes and events, and its ``lines_kept`` largest lines.  Walks the
    wire format (``XSpace.planes`` = 1; ``XPlane.name`` = 2, ``.lines`` =
    3; ``XLine.name`` = 2, ``.events`` = 4) and parses no event, so it
    costs a second where ``ProfileData`` costs ten: the size of a profile
    is what ``ProfilerSession.stop()`` and the reducer take their time by."""
    out: Dict[str, Dict[str, Any]] = {}
    for number, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        name, lines = "", []
        for field, payload in _fields(plane):
            if field == 2:
                name = bytes(payload).decode(errors="replace")
            elif field == 3:
                line_name, events = "", 0
                for inner, value in _fields(payload):
                    if inner == 2:
                        line_name = bytes(value).decode(errors="replace")
                    elif inner == 4:
                        events += 1
                lines.append((len(payload), events, line_name))
        lines.sort(reverse=True)
        out[name] = {
            "bytes": len(plane), "events": sum(n for _, n, _ in lines),
            "lines": {n: {"bytes": b, "events": e} for b, e, n in lines[:lines_kept]},
        }
    return out


# the stat of an op's XEventMetadata that carries jax's ``op_name``
# (``jit(_step)/transpose(jvp(TransformerNet))/attn0/q/dot_general:``
# on the v5e, a colon and the op's type behind the path)
OP_NAME_STAT = "tf_op"


def op_names_by_event(raw: bytes) -> Dict[str, Dict[str, str]]:
    """plane -> event name -> jax ``op_name``, for the device planes of a
    serialized profile.  ``ProfileData`` gives an event its own stats only;
    the ``op_name`` is a stat of the event's *metadata*, which every event
    of one HLO op shares, so it is read off the wire once per op
    (``XPlane.event_metadata`` = 4 and ``.stat_metadata`` = 5, maps whose
    entries hold the value under 2; ``XEventMetadata.name`` = 2, ``.stats``
    = 5; ``XStatMetadata.id`` = 1, ``.name`` = 2; ``XStat.metadata_id`` = 1,
    ``.str_value`` = 5, ``.ref_value`` = 7, a stat metadata's id whose name
    is the string)."""
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        plane_name, events, stat_names = "", [], {}
        for field, payload in _fields(plane):
            if field == 2:
                plane_name = bytes(payload).decode(errors="replace")
            elif field in (4, 5):
                value = next((v for n, v in _fields(payload) if n == 2), None)
                if value is None:
                    continue
                if field == 4:
                    events.append(value)
                else:
                    numbers, strings = _scalars(value)
                    stat_names[numbers.get(1, 0)] = strings.get(2, "")
        if not DEVICE_PLANE.match(plane_name):
            continue
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        names: Dict[str, str] = {}
        for event in events:
            event_name = op_name = ""
            for field, payload in _fields(event):
                if field == 2:
                    event_name = bytes(payload).decode(errors="replace")
                elif field == 5:
                    numbers, strings = _scalars(payload)
                    if numbers.get(1) in wanted:
                        op_name = strings.get(5) or stat_names.get(numbers.get(7, -1), "")
            if op_name:
                names[event_name] = op_name
        out[plane_name] = names
    return out


def _scalars(buf: memoryview) -> Tuple[Dict[int, int], Dict[int, str]]:
    """The varint fields and the string fields of one small message."""
    numbers: Dict[int, int] = {}
    strings: Dict[int, str] = {}
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            numbers[key >> 3], pos = _varint(buf, pos)
        elif kind == 2:
            size, pos = _varint(buf, pos)
            strings[key >> 3] = bytes(buf[pos:pos + size]).decode(errors="replace")
            pos += size
        else:
            pos += 8 if kind == 1 else 4
    return numbers, strings


def scopes_of(op_name: str, scopes: Sequence[str]) -> List[str]:
    """The ``scopes`` that are a path component of ``op_name``.  jax wraps
    the component a transform entered at (``transpose(jvp(attn0))``), so a
    component counts with its wrappers peeled; ``attn1`` is no component of
    ``.../attn10/...``."""
    parts = set()
    for part in op_name.rsplit(":", 1)[0].split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        parts.add(part)
    return [name for name in scopes if name in parts]


# ---------------------------------------------------------------------------
# interval arithmetic on (n, 2) arrays of [start, end)
# ---------------------------------------------------------------------------


def _as_array(intervals: Iterable[Sequence[float]]) -> np.ndarray:
    arr = np.asarray(list(intervals), dtype=np.float64).reshape(-1, 2)
    return arr[arr[:, 1] > arr[:, 0]]


def merge(intervals) -> np.ndarray:
    """Union of intervals as sorted, disjoint segments."""
    arr = _as_array(intervals)
    if not len(arr):
        return arr
    arr = arr[np.argsort(arr[:, 0], kind="stable")]
    reach = np.maximum.accumulate(arr[:, 1])
    first = np.ones(len(arr), bool)
    first[1:] = arr[1:, 0] > reach[:-1]
    starts = arr[first, 0]
    ends = np.append(reach[:-1][first[1:]], reach[-1])
    return np.stack([starts, ends], axis=1)


def clip(segments: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not len(segments):
        return segments
    out = np.stack([np.maximum(segments[:, 0], lo), np.minimum(segments[:, 1], hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def measure(segments: np.ndarray) -> float:
    return float((segments[:, 1] - segments[:, 0]).sum()) if len(segments) else 0.0


def complement(segments: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """What of [lo, hi) the (merged) segments leave uncovered."""
    segments = clip(segments, lo, hi)
    starts = np.append(lo, segments[:, 1])
    ends = np.append(segments[:, 0], hi)
    out = np.stack([starts, ends], axis=1)
    return out[out[:, 1] > out[:, 0]]


def subtract(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Merged segments ``a`` minus merged segments ``b``."""
    if not len(a):
        return a
    lo, hi = float(a[0, 0]), float(a[-1, 1])
    return intersect(a, complement(b, lo, hi))


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two merged segment lists."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return _as_array(out)


def measure_inside(segments: np.ndarray, windows: np.ndarray) -> np.ndarray:
    """Covered length of merged ``segments`` inside each [lo, hi) row of
    ``windows``, vectorised through the running covered length."""
    if not len(segments) or not len(windows):
        return np.zeros(len(windows))
    cum = np.concatenate([[0.0], np.cumsum(segments[:, 1] - segments[:, 0])])

    def covered_before(t):
        k = np.searchsorted(segments[:, 0], t, side="right")   # segments started
        full = cum[k]
        last = np.clip(k - 1, 0, len(segments) - 1)
        over = np.where(k > 0, np.maximum(segments[last, 1] - t, 0.0), 0.0)
        return full - over

    return covered_before(windows[:, 1]) - covered_before(windows[:, 0])


def self_times(ops: List[Interval]) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """For the nested events of one op line: (order, self_s, is_leaf) with
    ``order`` the indices sorted by start.  An event's self time is its
    duration minus its direct children's."""
    n = len(ops)
    starts = np.fromiter((o[1] for o in ops), np.float64, n)
    ends = np.fromiter((o[2] for o in ops), np.float64, n)
    order = np.lexsort((-ends, starts))
    self_s = (ends - starts)[order]
    leaf = np.ones(n, bool)
    stack: List[int] = []          # positions in `order`
    s_sorted, e_sorted = starts[order], ends[order]
    for pos in range(n):
        while stack and e_sorted[stack[-1]] <= s_sorted[pos]:
            stack.pop()
        if stack:
            parent = stack[-1]
            leaf[parent] = False
            self_s[parent] -= min(e_sorted[pos], e_sorted[parent]) - s_sorted[pos]
        stack.append(pos)
    return order, np.maximum(self_s, 0.0), leaf


def short_op_name(name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``%fusion.12 fusion bf16[..]``
    cut to a readable length."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:96]
    shape, _, call = rest.partition(" ")
    return ("%s %s %s" % (head, call.split("(", 1)[0], shape))[:96]


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _reduce_device(dev: Dict[str, List[Interval]], lo: float, hi: float,
                   scopes: Sequence[str] = ()) -> Dict[str, Any]:
    ops, modules, async_ops = dev["ops"], dev["modules"], dev.get("async_ops", [])
    op_names = dev.get("op_names") or [""] * len(ops)
    scope_table: Dict[str, List[float]] = {name: [0.0, 0] for name in scopes}
    scopes_cached: Dict[str, List[str]] = {}
    source = ops if ops else modules
    busy_segments = clip(merge((s, e) for _, s, e in source), lo, hi)
    busy = measure(busy_segments)

    programs: Dict[str, List[float]] = {}   # seconds, runs, whole seconds, whole runs
    if modules:
        touching = [(n, s, e) for n, s, e in modules if min(e, hi) > max(s, lo)]
        windows = clip(_as_array((s, e) for _, s, e in touching), lo, hi)
        inside = measure_inside(busy_segments, windows)
        for (name, s, e), seconds in zip(touching, inside):
            slot = programs.setdefault(name, [0.0, 0, 0.0, 0])
            slot[0] += float(seconds)
            slot[1] += 1
            if s >= lo and e <= hi:
                slot[2] += float(seconds)
                slot[3] += 1
    outside = busy - sum(v[0] for v in programs.values())
    if outside > 1e-9:
        programs[NO_PROGRAM] = [outside, 0, 0.0, 0]

    op_table: Dict[str, List[float]] = {}
    collective = exposed = 0.0
    if ops:
        order, self_s, leaf = self_times(ops)
        coll_iv, compute_iv = [], []
        for pos, idx in enumerate(order):
            name, s, e = ops[idx]
            if e <= lo or s >= hi:
                continue
            is_coll = bool(COLLECTIVE.match(name))
            if is_coll:
                coll_iv.append((s, e))
            elif leaf[pos]:
                compute_iv.append((s, e))
            if self_s[pos] > 0:
                slot = op_table.setdefault(short_op_name(name), [0.0, 0])
                slot[0] += float(self_s[pos])
                slot[1] += 1
                if scopes and op_names[idx]:
                    path = op_names[idx]
                    if path not in scopes_cached:
                        scopes_cached[path] = scopes_of(path, scopes)
                    for scope in scopes_cached[path]:
                        scope_table[scope][0] += float(self_s[pos])
                        scope_table[scope][1] += 1
        coll_iv += [(s, e) for n, s, e in async_ops if COLLECTIVE.match(n)]
        coll_segments = clip(merge(coll_iv), lo, hi)
        collective = measure(coll_segments)
        exposed = measure(subtract(coll_segments, clip(merge(compute_iv), lo, hi)))
    return {
        "busy_s": busy, "busy_segments": busy_segments, "programs": programs,
        "ops": op_table, "collective_s": collective,
        "collective_exposed_s": exposed, "scopes": scope_table,
    }


def name_gaps(gaps: np.ndarray, spans: List[HostSpan]) -> List[str]:
    """The innermost host span open at each gap's middle."""
    names = np.full(len(gaps), -1, np.int64)
    mids = gaps.mean(axis=1) if len(gaps) else np.zeros(0)
    order = np.argsort(mids, kind="stable")
    sorted_mids = mids[order]
    # outer spans first, so a span that starts later (inner) overwrites
    ranked = sorted(range(len(spans)), key=lambda i: (spans[i][1], -spans[i][2]))
    for i in ranked:
        _, s, e, _ = spans[i]
        a, b = np.searchsorted(sorted_mids, [s, e])
        names[order[a:b]] = i
    return [spans[i][0] if i >= 0 else NO_SPAN for i in names]


def reduce_trace(trace: Dict[str, Any], window: Optional[Tuple[float, float]] = None
                 ) -> Dict[str, Any]:
    """See the module docstring.  ``window`` is (start_s, end_s) on the
    trace's clock; absent, it is the extent of the device events."""
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    if window is None:
        every = [iv for d in devices.values() for iv in (d["ops"] or d["modules"])]
        if not every:
            raise ValueError("the trace holds no device event")
        window = (min(s for _, s, _ in every), max(e for _, _, e in every))
    lo, hi = window
    scopes = trace.get("scopes") or ()
    per_device = {n: _reduce_device(devices[n], lo, hi, scopes) for n in sorted(devices)}
    count = len(per_device)
    first = per_device[min(per_device)]

    programs: Dict[str, Dict[str, float]] = {}
    for dev in per_device.values():
        for name, totals in dev["programs"].items():
            slot = programs.setdefault(name, dict.fromkeys(PROGRAM_KEYS, 0.0))
            for key, total in zip(PROGRAM_KEYS, totals):
                slot[key] += total / count

    gaps = complement(first["busy_segments"], lo, hi)
    lengths = gaps[:, 1] - gaps[:, 0] if len(gaps) else np.zeros(0)
    long = lengths >= SHORT_GAP_S
    names = name_gaps(gaps[long], trace.get("host", []))
    idle_by_span: Dict[str, Dict[str, float]] = {}
    for name, length in zip(names, lengths[long]):
        slot = idle_by_span.setdefault(name, {"seconds": 0.0, "gaps": 0, "longest_s": 0.0})
        slot["seconds"] += float(length)
        slot["gaps"] += 1
        slot["longest_s"] = max(slot["longest_s"], float(length))
    if (~long).any():
        idle_by_span["short_gaps_under_20us"] = {
            "seconds": float(lengths[~long].sum()), "gaps": int((~long).sum()),
            "longest_s": float(lengths[~long].max()),
        }
    top = np.argsort(-lengths[long])[:10]
    longest = [(names[i], float(lengths[long][i]), float(gaps[long][i, 0] - lo)) for i in top]

    mean = lambda key: sum(d[key] for d in per_device.values()) / count  # noqa: E731
    by_scope: Dict[str, Dict[str, float]] = {}
    for name in scopes:
        seconds, ops = (sum(d["scopes"][name][i] for d in per_device.values()) / count
                        for i in (0, 1))
        if ops:         # a scope no op carries is left out: ``run.scope`` answers None
            by_scope[name] = {"seconds": seconds, "ops": ops}
    reduced = {
        "window_s": hi - lo,
        "chips": count,
        "busy_s": mean("busy_s"),
        "idle_s": (hi - lo) - mean("busy_s"),
        "programs": programs,
        "ops": {k: {"seconds": v[0], "runs": v[1]} for k, v in first["ops"].items()},
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "idle_by_span": idle_by_span,
        "longest_gaps": longest,
        "gaps": gaps,
        "host": [s for s in trace.get("host", []) if s[2] > lo and s[1] < hi],
        "per_device_busy_s": [d["busy_s"] for d in per_device.values()],
    }
    if scopes:
        reduced["scopes"] = by_scope
    return reduced


def breakdown(reduced: Dict[str, Any]) -> Dict[str, List[List[Any]]]:
    """The ``breakdown`` of a traced run's last line: the programs and ops
    with most device time (programs first; an op's time is its self time)
    and the idle time by host span, at most ten entries each."""
    programs = sorted(reduced["programs"].items(), key=lambda kv: -kv[1]["seconds"])
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1]["seconds"])
    n_prog = min(len(programs), 5)
    device_ops = [
        ["program %s x%d" % (name, round(v["runs"])), v["seconds"]]
        for name, v in programs[:n_prog]
    ] + [
        ["op %s x%d" % (name, v["runs"]), v["seconds"]]
        for name, v in ops[:10 - n_prog]
    ]
    idle = sorted(reduced["idle_by_span"].items(), key=lambda kv: -kv[1]["seconds"])
    idle_gaps = [
        ["%s x%d longest %.3fms" % (name, v["gaps"], v["longest_s"] * 1e3), v["seconds"]]
        for name, v in idle[:10]
    ]
    return {"device_ops": device_ops, "idle_gaps": idle_gaps}
