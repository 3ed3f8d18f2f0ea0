"""Split a traced benchmark run's device time under named scopes by HLO op.

Usage::

    python scripts/scope_split.py benchmark_out/<cell>/profile/trace.xplane.pb \
        attn gqa rope [-o split.json] [--program jit_device_rollout]

For each scope (a ``jax.named_scope`` that is a component of an op's
``op_name``, as ``benchmark/trace_reduce.py`` ``scopes_of`` reads it) and for
``outside`` (ops under none of the scopes given): the self time of the
device's ops inside the traced window, in milliseconds a run of the train
program (``jit__step``; ``--program`` names another, as the actor cell's
``jit_device_rollout``), summed by the op's kind (a fusion by its name's
stem: ``copy_dynamic-update-slice_fusion``) and, in the JSON, by phase
(``bwd``: the ``op_name`` holds ``transpose(``, which the replay of a
checkpoint does too) and result shape.  What ``attn_step_share``'s notes
give as one number a scope, op by op: which of a scope's milliseconds are
products, which re-layings (``copy``, ``slice``, ``dynamic-update-slice``).
The first chip's plane is read.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402

PROGRAM = "jit__step"
WINDOW = ("bench.window_begin", "bench.window_end")     # benchmark/harness.py's markers


def split(path, scopes, program=PROGRAM):
    """{"runs", "ms_per_run": {scope: {op kind: [ms, count]}}, "rows": [[ms,
    count, scope, phase, kind, shape], ...] by falling ms}."""
    trace = trace_reduce.load_xplane(path, scopes=scopes)
    begin, end = ([s for s in trace["host"] if s[0] == name] for name in WINDOW)
    lo, hi = (begin[0][2], end[-1][1]) if begin and end else (0.0, float("inf"))
    device = trace["devices"][min(trace["devices"])]
    ops, names = device["ops"], device["op_names"]
    runs = sum(program in name and min(e, hi) > max(s, lo)
               for name, s, e in device["modules"]) or 1
    order, self_s, _ = trace_reduce.self_times(ops)
    table = {}
    for pos, index in enumerate(order):
        name, start, stop = ops[index]
        if stop <= lo or start >= hi or self_s[pos] <= 0:
            continue
        head, _, rest = name.partition(" = ")
        kind = re.search(r" ([a-z][a-z0-9\-]*)\(", " " + rest)
        kind = kind.group(1) if kind else head
        if kind == "fusion":
            kind = "fusion:" + re.sub(r"[.\d]+$", "", head.lstrip("%"))
        phase = "bwd" if "transpose(" in names[index] else "fwd"
        for scope in trace_reduce.scopes_of(names[index], scopes) or ["outside"]:
            slot = table.setdefault((scope, phase, kind, rest.partition(" ")[0][:64]), [0.0, 0])
            slot[0] += float(self_s[pos])
            slot[1] += 1
    rows = sorted(([1e3 * s / runs, n / runs] + list(key) for key, (s, n) in table.items()),
                  reverse=True)
    by_kind = {}
    for ms, count, scope, _, kind, _ in rows:
        slot = by_kind.setdefault(scope, {}).setdefault(kind, [0.0, 0.0])
        slot[0] += ms
        slot[1] += count
    return {"runs": runs, "ms_per_run": by_kind, "rows": rows}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("xplane")
    parser.add_argument("scopes", nargs="+")
    parser.add_argument("-o", "--out", help="write the whole split here as JSON")
    parser.add_argument("--program", default=PROGRAM, help="the program whose runs divide the times")
    args = parser.parse_args(argv)
    result = split(args.xplane, args.scopes, args.program)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)
    for scope in args.scopes + ["outside"]:
        kinds = result["ms_per_run"].get(scope, {})
        print("== %s: %.3f ms a run over %d runs" % (
            scope, sum(ms for ms, _ in kinds.values()), result["runs"]))
        for kind, (ms, count) in sorted(kinds.items(), key=lambda kv: -kv[1][0])[:16]:
            print("   %8.3f ms  x%-7.1f %s" % (ms, count, kind))
    return 0


if __name__ == "__main__":
    sys.exit(main())
