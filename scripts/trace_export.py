"""Export trace.jsonl span files to Chrome trace-event JSON.

Usage::

    python scripts/trace_export.py trace.jsonl [trace.rank1.jsonl ...] \
        [-o trace_export.json]

The output opens directly in ``chrome://tracing`` or https://ui.perfetto.dev.
Each input file is one process's span stream (``handyrl_tpu/utils/trace.py``
writes one per rank); the files' ``__trace_meta__`` anchors (wall-clock +
monotonic pair) align ranks whose monotonic epochs differ — each process,
and each HOST, has its own monotonic zero, so cross-host spans can only be
placed on a shared axis through the wall clock.

Mapping (deterministic, golden-pinned by tests/test_trace.py):

* one complete event (``ph: "X"``) per span, ``ts``/``dur`` in
  microseconds relative to the earliest span across all inputs;
* ``pid`` = the span's rank (so Perfetto groups tracks per process),
  ``tid`` = a stable per-rank index over the sorted thread names;
* ``cat`` = ``setup`` for a set-up phase (``trace_phase``: the record says
  ``phase``) and ``compile`` for a ``compile.*`` event (a program traced,
  lowered, built or loaded, on the thread that waited for it); else the
  span's ``plane`` attr when present, else ``trace``;
* process/thread name metadata events (``ph: "M"``) label the tracks.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List

try:
    from handyrl_tpu.utils.trace import META_NAME, read_trace
except ImportError:  # standalone use outside the repo: same tail tolerance
    META_NAME = "__trace_meta__"

    def read_trace(path, strict=False):
        with open(path) as f:
            lines = f.readlines()
        out = []
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                if i == len(lines) - 1 and not strict:
                    break  # half-written tail from a killed run
                raise
        return out


def _category(record: Dict[str, Any]) -> str:
    if record.get("phase"):
        return "setup"
    if str(record.get("name", "")).startswith("compile."):
        return "compile"
    return str((record.get("attrs") or {}).get("plane", "trace"))


def export_chrome(record_lists: List[List[Dict[str, Any]]]) -> Dict[str, Any]:
    """Convert per-file span record lists into one Chrome trace dict."""
    # place every span on the shared wall-clock axis: wall_start =
    # t_mono + (meta.ts - meta.t_mono); a file with no meta (hand-built
    # fixtures) uses its monotonic values directly
    spans: List[Dict[str, Any]] = []
    for records in record_lists:
        meta = next((r for r in records if r.get("name") == META_NAME), None)
        offset = (meta["ts"] - meta["t_mono"]) if meta else 0.0
        for r in records:
            if r.get("name") == META_NAME:
                continue
            spans.append({
                "name": r.get("name", "?"),
                "start": float(r.get("t_mono", 0.0)) + offset,
                "dur": max(0.0, float(r.get("dur_s", 0.0))),
                "rank": int(r.get("rank", 0)),
                "thread": str(r.get("thread", "?")),
                "attrs": r.get("attrs") or {},
                "cat": _category(r),
            })
    base = min((s["start"] for s in spans), default=0.0)
    threads: Dict[int, List[str]] = {}
    for s in spans:
        names = threads.setdefault(s["rank"], [])
        if s["thread"] not in names:
            names.append(s["thread"])
    tid_of = {
        (rank, name): i
        for rank, names in threads.items()
        for i, name in enumerate(sorted(names))
    }
    events: List[Dict[str, Any]] = []
    for rank in sorted(threads):
        events.append({
            "name": "process_name", "ph": "M", "pid": rank, "tid": 0,
            "args": {"name": f"rank {rank}"},
        })
        for name in sorted(threads[rank]):
            events.append({
                "name": "thread_name", "ph": "M", "pid": rank,
                "tid": tid_of[(rank, name)], "args": {"name": name},
            })
    for s in sorted(spans, key=lambda s: (s["rank"], s["start"], s["name"])):
        events.append({
            "name": s["name"],
            "cat": s["cat"],
            "ph": "X",
            "ts": round((s["start"] - base) * 1e6, 3),
            "dur": round(s["dur"] * 1e6, 3),
            "pid": s["rank"],
            "tid": tid_of[(s["rank"], s["thread"])],
            "args": s["attrs"],
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("traces", nargs="+", help="trace.jsonl file(s), one per rank")
    ap.add_argument("-o", "--out", default="trace_export.json",
                    help="output path (Chrome trace-event JSON)")
    args = ap.parse_args(argv)
    record_lists = [read_trace(path) for path in args.traces]
    n_spans = sum(
        1 for recs in record_lists for r in recs if r.get("name") != META_NAME
    )
    out = export_chrome(record_lists)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(
        f"wrote {args.out}: {n_spans} span(s) from {len(record_lists)} "
        "file(s) — open in chrome://tracing or ui.perfetto.dev"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
