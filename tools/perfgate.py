"""perfgate: the perf-regression CI gate over bench snapshots (ROADMAP #6).

Judges a ``bench_snapshot.json`` against a banked capture (``BENCH_*.json``)
the way graftlint judges invariants: mechanically, with an explicit
sensitivity class per metric and a content-addressed baseline for
burn-down.  The class system encodes BASELINE.md's measured lesson — the
round-5 capture moved ABSOLUTE single-dispatch rates 0.6x on identical
code (session variance), while same-session internal ratios stayed
put — so:

* **hard** class: ratio-of-internal-baseline metrics (``*_frac``,
  ``*_ratio``, ``*_coverage``, ``speedup``, ``*_dropped``) and
  categorical pins (``*_target_met``, ``*_mode``, ``*_attn``).  These
  compare two measurements from the SAME session, so session variance
  divides out; a move past ``--hard-tol`` is a code regression and FAILS
  the gate.
* **soft** class: absolute throughput/latency (``*_per_sec``, ``*_qps``,
  ``*_mfu``, ``*_ms``, ``*_vs_*``).  Session variance is real here; only
  a move past ``--soft-tol`` (default 2x) is even reported as a
  regression, and soft regressions never fail the gate on their own.
* **info**: everything else (counts, run lengths, shapes) — reported,
  never gated.

A banked hard/exact metric MISSING from the current snapshot also fails
in enforcing mode (a crashed stage's numbers simply vanish — the exact
regression class a perf gate exists to catch); ``--allow-missing`` is
the explicit escape for a deliberate ``BENCH_STAGES`` subset.

Cross-platform comparisons (a CPU smoke vs a TPU capture) are forced to
ADVISORY: the report still prints, the exit code stays 0.  ``--advisory``
forces the same for same-platform runs — the CI mode until BENCH_r06 is
banked (docs/observability.md documents the flip to enforcing).

Baseline burn-down (graftlint discipline): ``--baseline FILE`` suppresses
grandfathered regression fingerprints and reports stale entries;
``--write-baseline`` banks the current regressions.  Fingerprints are
content-addressed (metric + class + direction), immune to report-order
drift.

Usage::

    python -m tools.perfgate bench_snapshot.json --against BENCH_r05.json
    python -m tools.perfgate bench_snapshot.json --against BENCH_r05.json \
        --advisory --baseline tools/PERFGATE_BASELINE.json

Exit codes: 0 = clean (or advisory), 1 = hard-class regression
(enforcing mode), 2 = usage / unreadable snapshot.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
from typing import Any, Dict, List, Optional, Tuple

HARD_SUFFIXES = ("_frac", "_ratio", "_coverage", "speedup", "_dropped")
SOFT_SUFFIXES = ("_per_sec", "_qps", "_mfu", "_ms")
EXACT_SUFFIXES = ("_target_met", "_mode", "_attn")
# numeric metrics where SMALLER is better (everything else: bigger)
LOWER_BETTER_MARKERS = (
    "input_wait_frac", "rollout_time_frac", "shed_rate", "deadline_miss",
    "_dropped", "_p50_ms", "_p99_ms", "warm_ms", "_ttfr_ms",
)


def classify(key: str, value: Any) -> Tuple[str, int]:
    """(class, direction) for one metric: class in hard/soft/exact/info,
    direction +1 bigger-is-better / -1 smaller-is-better (0 for exact)."""
    if isinstance(value, bool):
        return "exact", 0
    if isinstance(value, str):
        return ("exact", 0) if key.endswith(EXACT_SUFFIXES) else ("info", 0)
    if not isinstance(value, (int, float)) or value is None:
        return "info", 0
    direction = -1 if any(m in key for m in LOWER_BETTER_MARKERS) else 1
    if key.endswith(HARD_SUFFIXES):
        return "hard", direction
    if key.endswith(SOFT_SUFFIXES) or "_vs_" in key or key.endswith("_vs_baseline"):
        return "soft", direction
    return "info", direction


def fingerprint(key: str, cls: str, direction: int) -> str:
    digest = hashlib.sha1(f"{key}:{cls}:{direction}".encode()).hexdigest()[:12]
    return f"PERF:{key}:{digest}"


# -- snapshot loading ---------------------------------------------------------


def _flatten(record: Dict[str, Any]) -> Tuple[Dict[str, Any], Optional[str]]:
    """bench snapshot record -> ({metric: value}, platform)."""
    out: Dict[str, Any] = {}
    if record.get("metric") and record.get("value") is not None:
        out[str(record["metric"])] = record["value"]
    for key, value in (record.get("extra") or {}).items():
        if isinstance(value, dict):
            for k2, v2 in value.items():
                out[f"{key}_{k2}"] = v2
        elif isinstance(value, list):
            continue  # stages_skipped etc. — not metrics
        else:
            out[key] = value
    return out, record.get("platform")


def load_snapshot(path: str) -> Tuple[Dict[str, Any], Optional[str]]:
    """Load metrics from a bench_snapshot.json, a banked ``BENCH_*.json``
    capture ({n, cmd, rc, tail}: the newest parseable snapshot line in the
    tail wins), or a plain flat {metric: value} dict (tests)."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a JSON object")
    if "tail" in data and "cmd" in data:
        tail = str(data.get("tail") or "")
        for line in reversed(tail.splitlines()):
            idx = line.find('{"metric"')
            if idx >= 0:
                try:
                    return _flatten(json.loads(line[idx:]))
                except ValueError:
                    pass
            # the tail window often starts MID-record (it is the last N
            # bytes of stdout, and one snapshot line is the whole record):
            # recover the intact suffix — the "extra" object carries every
            # stage metric, and platform rides a scalar field before it
            idx = line.find('"extra": {')
            if idx >= 0:
                try:
                    extra, _ = json.JSONDecoder().raw_decode(
                        line[idx + len('"extra": '):]
                    )
                except ValueError:
                    continue
                import re

                m = re.search(r'"platform":\s*"([^"]*)"', line)
                return _flatten({
                    "extra": extra,
                    "platform": m.group(1) if m else None,
                })
        raise ValueError(
            f"{path}: banked capture holds no parseable snapshot line "
            "(the bench emits one full JSON record per stage)"
        )
    if "metric" in data or "extra" in data:
        return _flatten(data)
    platform = data.pop("platform", None)
    return data, platform


# -- judgment -----------------------------------------------------------------


class Verdict:
    __slots__ = ("key", "cls", "direction", "base", "cur", "status", "note")

    def __init__(self, key, cls, direction, base, cur, status, note=""):
        self.key, self.cls, self.direction = key, cls, direction
        self.base, self.cur, self.status, self.note = base, cur, status, note

    @property
    def fingerprint(self) -> str:
        return fingerprint(self.key, self.cls, self.direction)

    def format(self) -> str:
        tag = {"hard": "HARD", "soft": "soft", "exact": "PIN ",
               "info": "info"}[self.cls]
        return f"  {tag}  {self.key}: {self.base!r} -> {self.cur!r} {self.note}"


def judge(baseline: Dict[str, Any], current: Dict[str, Any],
          hard_tol: float, soft_tol: float) -> List[Verdict]:
    """Compare every baseline metric against the current snapshot."""
    verdicts: List[Verdict] = []
    for key in sorted(baseline):
        base = baseline[key]
        cls, direction = classify(key, base)
        if key not in current:
            verdicts.append(Verdict(key, cls, direction, base, None, "missing",
                                    "(not in current snapshot)"))
            continue
        cur = current[key]
        if cls == "info":
            verdicts.append(Verdict(key, cls, direction, base, cur, "info"))
            continue
        if cls == "exact":
            if isinstance(base, bool):
                # True -> False is the regression; False -> True is progress
                bad = bool(base) and not bool(cur)
            else:
                bad = base != cur
            verdicts.append(Verdict(
                key, cls, direction, base, cur,
                "regressed" if bad else "ok",
                "(pinned value moved)" if bad else "",
            ))
            continue
        try:
            base_f, cur_f = float(base), float(cur)
        except (TypeError, ValueError):
            verdicts.append(Verdict(key, cls, direction, base, cur, "info",
                                    "(non-numeric)"))
            continue
        tol = hard_tol if cls == "hard" else soft_tol
        if base_f == 0.0:
            # no ratio exists: a lower-is-better zero (dropped requests)
            # regressing to nonzero is real; a higher-is-better zero is
            # uninformative
            if direction < 0 and cur_f > 0:
                verdicts.append(Verdict(key, cls, direction, base, cur,
                                        "regressed", "(was 0)"))
            else:
                verdicts.append(Verdict(key, cls, direction, base, cur, "ok"))
            continue
        ratio = cur_f / base_f
        if direction > 0:
            regressed, improved = ratio < 1.0 - tol, ratio > 1.0 + tol
        else:
            regressed, improved = ratio > 1.0 + tol, ratio < 1.0 - tol
        status = "regressed" if regressed else "improved" if improved else "ok"
        verdicts.append(Verdict(key, cls, direction, base, cur, status,
                                f"({ratio:.2f}x, tol {tol:.2f})"))
    return verdicts


# -- baseline (graftlint-style burn-down) -------------------------------------


def load_baseline(path: str) -> set:
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "findings" not in data:
        raise ValueError(f"{path}: not a perfgate baseline (missing 'findings')")
    return {fp for fps in data["findings"].values() for fp in fps}


def write_baseline(path: str, regressions: List[Verdict]) -> None:
    payload = {
        "version": 1,
        "findings": {"PERFGATE": sorted(v.fingerprint for v in regressions)},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


# -- CLI ----------------------------------------------------------------------


def run(current_path: str, against_path: str, advisory: bool = False,
        hard_tol: float = 0.10, soft_tol: float = 0.50,
        baseline_path: Optional[str] = None, write_baseline_path: Optional[str] = None,
        force_platform: bool = False, allow_missing: bool = False,
        out=sys.stdout) -> int:
    try:
        current, cur_platform = load_snapshot(current_path)
        banked, base_platform = load_snapshot(against_path)
    except (OSError, ValueError) as exc:
        print(f"perfgate: cannot load snapshots: {exc}", file=sys.stderr)
        return 2
    platform_mismatch = (
        cur_platform and base_platform and cur_platform != base_platform
    )
    if platform_mismatch and not force_platform:
        advisory = True
    verdicts = judge(banked, current, hard_tol, soft_tol)

    suppressed: List[Verdict] = []
    stale: set = set()
    if baseline_path:
        try:
            grandfathered = load_baseline(baseline_path)
        except (OSError, ValueError) as exc:
            print(f"perfgate: bad baseline: {exc}", file=sys.stderr)
            return 2
        kept = []
        seen = set()
        for v in verdicts:
            if v.status == "regressed" and v.fingerprint in grandfathered:
                suppressed.append(v)
                seen.add(v.fingerprint)
            else:
                kept.append(v)
        stale = grandfathered - seen
        verdicts = kept

    regressions = [v for v in verdicts if v.status == "regressed"]
    hard = [v for v in regressions if v.cls in ("hard", "exact")]
    soft = [v for v in regressions if v.cls == "soft"]
    improved = [v for v in verdicts if v.status == "improved"]
    missing = [v for v in verdicts if v.status == "missing"]
    # a stage that crashes or stops emitting numbers is the regression
    # class this gate exists to catch — its banked hard/exact metrics
    # simply VANISH from the current snapshot, so in enforcing mode a
    # missing hard-class metric fails like a regressed one (stage subsets
    # pass --allow-missing explicitly)
    missing_hard = [
        v for v in missing
        if v.cls in ("hard", "exact") and not allow_missing
    ]

    print(
        f"perfgate: {current_path} ({cur_platform or '?'}) judged against "
        f"{against_path} ({base_platform or '?'})"
        + (" [ADVISORY: platform mismatch]" if platform_mismatch else
           " [ADVISORY]" if advisory else ""),
        file=out,
    )
    for v in regressions:
        print(v.format() + "  REGRESSED", file=out)
    for v in improved:
        print(v.format() + "  improved", file=out)
    if missing_hard and not advisory:
        for v in missing_hard:
            print(f"  MISS  {v.key} ({v.cls}): banked but absent from the "
                  "current snapshot — a vanished stage fails the gate "
                  "(pass --allow-missing for a deliberate stage subset)",
                  file=out)
    if missing:
        print(f"  ({len(missing)} banked metric(s) absent from the current "
              "snapshot — stage subset or skipped stages)", file=out)
    for v in suppressed:
        print(v.format() + "  suppressed (baselined — burn down)", file=out)
    for fp in sorted(stale):
        print(f"  stale baseline entry {fp} (matches nothing — delete it)",
              file=out)
    print(
        f"perfgate: {len(hard)} hard / {len(soft)} soft regression(s), "
        f"{len(improved)} improved, {len(missing)} missing, "
        f"{len(suppressed)} suppressed",
        file=out,
    )

    if write_baseline_path:
        write_baseline(write_baseline_path, regressions)
        print(f"perfgate: wrote baseline {write_baseline_path} "
              f"({len(regressions)} fingerprint(s))", file=out)

    if (hard or missing_hard) and not advisory:
        print(
            "perfgate: FAIL ("
            + ("hard-class regression" if hard else "hard-class metric missing")
            + ")",
            file=out,
        )
        return 1
    print("perfgate: " + ("ADVISORY" if advisory and (hard or soft) else "PASS"),
          file=out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.perfgate",
        description="perf-regression gate over bench snapshots",
    )
    ap.add_argument("current", help="bench_snapshot.json (or banked capture)")
    ap.add_argument("--against", required=True,
                    help="banked capture to judge against (BENCH_*.json)")
    ap.add_argument("--advisory", action="store_true",
                    help="report but never fail (CI mode until the next "
                    "same-platform capture is banked)")
    ap.add_argument("--hard-tol", type=float, default=0.10,
                    help="hard-class relative tolerance (default 0.10)")
    ap.add_argument("--soft-tol", type=float, default=0.50,
                    help="soft-class relative tolerance (default 0.50)")
    ap.add_argument("--baseline", default=None,
                    help="grandfathered-regression baseline JSON (burn-down)")
    ap.add_argument("--write-baseline", default=None,
                    help="bank the current regressions as the baseline")
    ap.add_argument("--force-platform", action="store_true",
                    help="gate even across differing platform strings")
    ap.add_argument("--allow-missing", action="store_true",
                    help="deliberate stage subset: banked hard-class "
                    "metrics absent from the current snapshot do not fail")
    args = ap.parse_args(argv)
    return run(
        args.current, args.against, advisory=args.advisory,
        hard_tol=args.hard_tol, soft_tol=args.soft_tol,
        baseline_path=args.baseline, write_baseline_path=args.write_baseline,
        force_platform=args.force_platform, allow_missing=args.allow_missing,
    )


if __name__ == "__main__":
    sys.exit(main())
