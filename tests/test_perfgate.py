"""tools/perfgate.py: the perf-regression CI gate (ROADMAP item 6).

Pins the acceptance contract: the gate PASSES the banked captures (a
capture judged against itself is clean), FAILS a synthetically regressed
snapshot on a hard-class metric, treats absolute-throughput moves as
soft (the r5 capture's absolutes moved 0.6x on identical code — session
variance, not regressions), goes advisory across platforms, and carries the
graftlint-style content-addressed baseline for burn-down.
"""

import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.obs

from tools.perfgate import (
    classify,
    fingerprint,
    judge,
    load_snapshot,
    run,
)

REPO = Path(__file__).parent.parent
R05 = str(REPO / "BENCH_r05.json")


# -- sensitivity classes ------------------------------------------------------


@pytest.mark.parametrize("key,value,want_cls,want_dir", [
    # hard: ratio-of-internal-baseline — session variance divides out
    ("northstar2_per_chip_frac", 1.14, "hard", 1),
    ("northstar2_produce_consume_ratio", 0.015, "hard", 1),
    ("league_payoff_coverage", 1.0, "hard", 1),
    ("flash_attention_speedup", 1.54, "hard", 1),
    ("serving_swap_dropped", 0, "hard", -1),
    ("northstar2_rollout_time_frac", 0.91, "hard", -1),
    ("geese_input_wait_frac", 0.17, "hard", -1),
    # soft: absolute throughput/latency — BASELINE.md's 0.6x-on-identical-
    # code lesson
    ("tictactoe_updates_per_sec", 506.0, "soft", 1),
    ("serving_saturation_qps", 6400.0, "soft", 1),
    ("geese_mfu", 0.18, "soft", 1),
    ("serving_p99_ms", 7.1, "soft", -1),
    ("device_selfplay_vs_reference_gen", 6613.0, "soft", 1),
    # exact pins: categorical values must not move
    ("transformer_long_target_met", True, "exact", 0),
    ("northstar4_device_mode", "device", "exact", 0),
    ("transformer_long_T512_auto_attn", "flash", "exact", 0),
    # info: counts / run lengths / shapes — reported, never gated
    ("league_run_seconds", 8.9, "info", 1),
    ("transformer_net", "d1536 L8 H16", "info", 0),
    ("geese_flops_per_step", 9.4e10, "info", 1),
])
def test_classification_table(key, value, want_cls, want_dir):
    cls, direction = classify(key, value)
    assert (cls, direction) == (want_cls, want_dir), key


# -- judgment -----------------------------------------------------------------


def test_hard_regression_detected_soft_variance_tolerated():
    base = {
        "northstar2_per_chip_frac": 1.0,
        "tictactoe_updates_per_sec": 1000.0,
    }
    # the r5 story: absolutes at 0.6x (session variance), internal ratio intact -> OK
    ok = judge(base, {"northstar2_per_chip_frac": 0.98,
                      "tictactoe_updates_per_sec": 600.0}, 0.10, 0.50)
    assert all(v.status in ("ok",) for v in ok)
    # the internal ratio collapsing IS a code regression
    bad = judge(base, {"northstar2_per_chip_frac": 0.5,
                       "tictactoe_updates_per_sec": 1000.0}, 0.10, 0.50)
    hard = [v for v in bad if v.status == "regressed"]
    assert [v.key for v in hard] == ["northstar2_per_chip_frac"]
    assert hard[0].cls == "hard"
    # an absolute falling past soft tolerance is at least REPORTED
    soft = judge(base, {"northstar2_per_chip_frac": 1.0,
                        "tictactoe_updates_per_sec": 100.0}, 0.10, 0.50)
    assert [v.key for v in soft if v.status == "regressed"] == [
        "tictactoe_updates_per_sec"
    ]


def test_lower_is_better_and_zero_baselines():
    base = {"serving_p99_ms": 10.0, "serving_swap_dropped": 0,
            "geese_input_wait_frac": 0.05}
    vs = judge(base, {"serving_p99_ms": 9.0, "serving_swap_dropped": 3,
                      "geese_input_wait_frac": 0.30}, 0.10, 0.50)
    by = {v.key: v for v in vs}
    assert by["serving_p99_ms"].status == "ok"          # got faster
    assert by["serving_swap_dropped"].status == "regressed"  # was 0
    assert by["serving_swap_dropped"].cls == "hard"
    assert by["geese_input_wait_frac"].status == "regressed"  # 6x the wait


def test_exact_pins():
    base = {"transformer_long_target_met": True,
            "northstar4_device_mode": "device"}
    vs = judge(base, {"transformer_long_target_met": False,
                      "northstar4_device_mode": "shm"}, 0.10, 0.50)
    assert all(v.status == "regressed" and v.cls == "exact" for v in vs)
    # False -> True is progress, not a pin violation
    vs = judge({"x_target_met": False}, {"x_target_met": True}, 0.10, 0.50)
    assert vs[0].status == "ok"


def test_missing_keys_reported_not_failed():
    vs = judge({"a_per_chip_frac": 1.0}, {}, 0.10, 0.50)
    assert vs[0].status == "missing"


def test_missing_hard_metric_fails_enforcing_unless_allowed(tmp_path):
    """A stage that crashes or stops emitting numbers makes its banked
    hard metrics VANISH — the exact regression class the gate exists to
    catch, so enforcing mode fails on it; --allow-missing is the explicit
    escape for a deliberate BENCH_STAGES subset."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps({
        "serving_saturation_qps": 6400.0,        # soft: may go missing
        "northstar2_per_chip_frac": 1.14,        # hard: must not vanish
    }))
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps({"serving_saturation_qps": 6000.0}))
    buf = io.StringIO()
    assert run(str(cur), str(base), out=buf) == 1
    assert "northstar2_per_chip_frac" in buf.getvalue()
    assert run(str(cur), str(base), allow_missing=True, out=io.StringIO()) == 0
    assert run(str(cur), str(base), advisory=True, out=io.StringIO()) == 0
    # a missing SOFT metric alone never fails
    cur2 = tmp_path / "cur2.json"
    cur2.write_text(json.dumps({"northstar2_per_chip_frac": 1.10}))
    assert run(str(cur2), str(base), out=io.StringIO()) == 0


# -- snapshot loading ---------------------------------------------------------


def _record_snapshot(tmp_path) -> str:
    """A snapshot in the record form bench.py writes (``_emit_snapshot``:
    one JSON line, stage metrics under "extra") from another platform
    than the banked capture's."""
    path = tmp_path / "bench_snapshot.json"
    path.write_text(json.dumps({
        "metric": "tictactoe_trained_env_steps_per_sec", "value": 1000.0,
        "unit": "env-steps/s", "vs_baseline": 0.03,
        "platform": "cpu:cpu x4", "error": None,
        "extra": {"league_autovec_per_chip_frac": 0.9,
                  "northstar2_per_chip_frac": 0.2},
    }) + "\n")
    return str(path)


def test_loads_banked_capture_and_flat_snapshot(tmp_path):
    metrics, platform = load_snapshot(R05)
    assert platform == "tpu:TPU v5 lite x1"
    assert metrics["northstar2_per_chip_frac"] == 1.14
    assert metrics["flash_attention_speedup"] == 1.54  # nested dict flattened
    # bench.py's own snapshot side file (record form)
    metrics2, platform2 = load_snapshot(_record_snapshot(tmp_path))
    assert "league_autovec_per_chip_frac" in metrics2
    assert platform2 and platform2 != platform
    # flat dict (synthetic)
    p = tmp_path / "flat.json"
    p.write_text(json.dumps({"platform": "x", "k_frac": 1.0}))
    m3, p3 = load_snapshot(str(p))
    assert m3 == {"k_frac": 1.0} and p3 == "x"


# -- the gate end to end ------------------------------------------------------


def _regressed_r05(tmp_path) -> str:
    """BENCH_r05 with one hard-class metric synthetically collapsed."""
    metrics, platform = load_snapshot(R05)
    metrics["northstar2_per_chip_frac"] = metrics["northstar2_per_chip_frac"] * 0.4
    out = tmp_path / "regressed.json"
    out.write_text(json.dumps(dict(metrics, platform=platform)))
    return str(out)


def test_banked_capture_passes_against_itself():
    buf = io.StringIO()
    assert run(R05, R05, out=buf) == 0
    assert "PASS" in buf.getvalue()
    assert "REGRESSED" not in buf.getvalue()


def test_synthetic_hard_regression_fails_enforcing_passes_advisory(tmp_path):
    bad = _regressed_r05(tmp_path)
    buf = io.StringIO()
    assert run(bad, R05, out=buf) == 1
    text = buf.getvalue()
    assert "northstar2_per_chip_frac" in text and "FAIL" in text
    # advisory mode (the CI stance until BENCH_r06 is banked): reported,
    # never failed
    buf = io.StringIO()
    assert run(bad, R05, advisory=True, out=buf) == 0
    assert "northstar2_per_chip_frac" in buf.getvalue()


def test_platform_mismatch_forces_advisory(tmp_path):
    """A snapshot from another platform judged against the TPU capture
    must never fail CI — the numbers are not comparable, only reportable."""
    buf = io.StringIO()
    rc = run(_record_snapshot(tmp_path), R05, out=buf)
    assert rc == 0
    assert "ADVISORY" in buf.getvalue()


def test_baseline_burn_down_round_trip(tmp_path):
    bad = _regressed_r05(tmp_path)
    baseline = tmp_path / "PERFGATE_BASELINE.json"
    buf = io.StringIO()
    # bank the known regression...
    assert run(bad, R05, write_baseline_path=str(baseline), out=buf) == 1
    fps = json.loads(baseline.read_text())["findings"]["PERFGATE"]
    assert fps == [fingerprint("northstar2_per_chip_frac", "hard", 1)]
    # ...now it suppresses (burn-down list), and the gate passes
    buf = io.StringIO()
    assert run(bad, R05, baseline_path=str(baseline), out=buf) == 0
    assert "suppressed" in buf.getvalue()
    # a fixed regression turns the entry STALE so the baseline shrinks
    buf = io.StringIO()
    assert run(R05, R05, baseline_path=str(baseline), out=buf) == 0
    assert "stale baseline entry" in buf.getvalue()


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(REPO))
    ok = subprocess.run(
        [sys.executable, "-m", "tools.perfgate", R05, "--against", R05],
        capture_output=True, text=True, cwd=str(REPO), env=env,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "tools.perfgate", _regressed_r05(tmp_path),
         "--against", R05],
        capture_output=True, text=True, cwd=str(REPO), env=env,
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    garbage = tmp_path / "garbage.json"
    garbage.write_text("[]")
    usage = subprocess.run(
        [sys.executable, "-m", "tools.perfgate", str(garbage), "--against", R05],
        capture_output=True, text=True, cwd=str(REPO), env=env,
    )
    assert usage.returncode == 2, usage.stdout + usage.stderr
