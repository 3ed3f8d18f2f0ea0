"""Unit coverage for bench.py's capture-reliability layer: the device
lookup that refuses a CPU, the stage retry with partial-result rollback,
the stage filter, and the incremental snapshots under the outer deadline.
No accelerator is touched.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import bench


@pytest.fixture(autouse=True)
def _fast_sleep(monkeypatch):
    """A failed stage sleeps before its retry; record instead."""
    sleeps = []
    monkeypatch.setattr(bench.time, "sleep", sleeps.append)
    yield sleeps


@pytest.fixture(autouse=True)
def _snapshot_tmp(monkeypatch, tmp_path):
    """Stage runs now emit snapshot side files; keep them out of the repo."""
    monkeypatch.setenv("BENCH_SNAPSHOT", str(tmp_path / "snap.json"))
    yield tmp_path / "snap.json"


def test_env_float_parses_and_falls_back(monkeypatch):
    monkeypatch.delenv("X_BENCH_T", raising=False)
    assert bench._env_float("X_BENCH_T", 7.5) == 7.5
    monkeypatch.setenv("X_BENCH_T", "3")
    assert bench._env_float("X_BENCH_T", 7.5) == 3.0
    monkeypatch.setenv("X_BENCH_T", "junk")
    assert bench._env_float("X_BENCH_T", 7.5) == 7.5
    # set-but-empty (CI interpolation of an unset variable) means default,
    # NOT 0 — 0 would silently disable the deadline
    monkeypatch.setenv("X_BENCH_T", "")
    assert bench._env_float("X_BENCH_T", 7.5) == 7.5
    monkeypatch.setenv("X_BENCH_T", "0")
    assert bench._env_float("X_BENCH_T", 7.5) == 0.0


def test_device_lookup_raises_without_accelerator():
    """A measurement path that finds no chip fails; it never falls back
    to the CPU (this suite runs with JAX_PLATFORMS=cpu)."""
    with pytest.raises(RuntimeError, match="only the CPU"):
        bench._accelerator_devices()


def test_device_lookup_returns_accelerator_devices(monkeypatch):
    import jax
    from types import SimpleNamespace

    chips = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    monkeypatch.setattr(jax, "devices", lambda: chips)
    assert bench._accelerator_devices() is chips


def test_main_without_accelerator_raises_and_writes_no_rate(
    monkeypatch, capsys, _snapshot_tmp
):
    """bench.py with no accelerator exits non-zero (the raise) and writes
    no rate: nothing on stdout, no snapshot side file."""
    monkeypatch.delenv("BENCH_STAGES", raising=False)
    monkeypatch.setattr(
        "handyrl_tpu.utils.enable_compile_cache", lambda: None
    )
    with pytest.raises(RuntimeError, match="only the CPU"):
        bench.main()
    assert capsys.readouterr().out == ""
    assert not _snapshot_tmp.exists()


def test_main_rejects_unknown_stage_before_any_device_work(monkeypatch, capsys):
    monkeypatch.setenv("BENCH_STAGES", "no-such-stage")
    monkeypatch.setattr(
        bench, "_accelerator_devices",
        lambda: pytest.fail("device lookup ran for a typo'd stage filter"),
    )
    with pytest.raises(SystemExit, match="no-such-stage"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_no_exit_zero_escape_hatch_in_bench():
    """The watchdogs left through os._exit(0): a hung run read as a clean
    one.  Nothing in bench.py may leave that way again."""
    src = Path(bench.__file__).read_text()
    assert "os._exit" not in src
    assert "jax_platforms" not in src  # no in-code switch to the CPU either


def test_run_stage_rolls_back_partial_writes(_fast_sleep):
    """A stage that dies after recording throughput must not leave numbers
    that read as measured; every attempt's traceback is kept."""
    result = {"value": None, "vs_baseline": None, "error": None, "extra": {}}
    calls = []

    def stage():
        calls.append(1)
        result["extra"]["partial"] = 123
        result["value"] = 999.0
        raise RuntimeError(f"boom{len(calls)}")

    out = bench._run_stage(result, "s", stage, retry_delay=0.0)
    assert out is None and len(calls) == 2
    assert "partial" not in result["extra"] and result["value"] is None
    assert "attempt 1" in result["error"] and "attempt 2" in result["error"]
    assert "boom1" in result["error"] and "boom2" in result["error"]


def test_run_stage_retry_succeeds_and_keeps_writes(_fast_sleep):
    result = {"value": None, "vs_baseline": None, "error": None, "extra": {}}
    calls = []

    def stage():
        calls.append(1)
        if len(calls) == 1:
            result["extra"]["junk"] = 1  # partial write from the failure
            raise ConnectionRefusedError("remote_compile: Connection refused")
        result["extra"]["rate"] = 42.0
        return "ok"

    assert bench._run_stage(result, "s", stage, retry_delay=0.0) == "ok"
    assert result["error"] is None
    assert result["extra"] == {"rate": 42.0}


def test_stage_filter_parsing(monkeypatch):
    monkeypatch.delenv("BENCH_STAGES", raising=False)
    assert bench._stage_filter() is None
    # set-but-empty (CI interpolation) means all stages, not none
    monkeypatch.setenv("BENCH_STAGES", "")
    assert bench._stage_filter() is None
    monkeypatch.setenv("BENCH_STAGES", "transformer, flash")
    assert bench._stage_filter() == {"transformer", "flash"}


def test_stage_filter_expands_dependencies(monkeypatch):
    """BENCH_STAGES=northstar2 must also run geese-train: the dependent
    stages are gated on its result in main() and would otherwise be
    silently skipped with no numbers and no note."""
    monkeypatch.setenv("BENCH_STAGES", "northstar2")
    assert bench._stage_filter() == {"northstar2", "geese-train"}
    # the dependency map only names real stages
    for k, deps in bench.STAGE_DEPS.items():
        assert k in bench.KNOWN_STAGES
        assert set(deps) <= set(bench.KNOWN_STAGES)


def test_stage_filter_skips_unlisted_stages(monkeypatch, _fast_sleep):
    """With BENCH_STAGES set, unlisted stages never run (their fn is not
    called) and are recorded in extra.stages_skipped; listed ones run."""
    monkeypatch.setenv("BENCH_STAGES", "keep")
    result = {"value": None, "vs_baseline": None, "error": None, "extra": {}}
    ran = []
    assert bench._run_stage(result, "drop", lambda: ran.append("drop")) is None
    assert bench._run_stage(result, "keep", lambda: ran.append("keep") or "ok") == "ok"
    assert ran == ["keep"]
    assert result["extra"]["stages_skipped"] == ["drop"]
    assert result["error"] is None


def test_known_stages_matches_run_stage_call_sites():
    """KNOWN_STAGES is the BENCH_STAGES validation whitelist; a stage
    added to main() without updating it would be impossible to select
    (the filter would reject its name as unknown).  Parse the source for
    _run_stage call sites and pin exact agreement."""
    import re
    from pathlib import Path

    src = Path(bench.__file__).read_text()
    called = set(re.findall(r'_run_stage\(result, "([^"]+)"', src))
    assert called == set(bench.KNOWN_STAGES), (
        f"KNOWN_STAGES drift: called-but-unknown {called - set(bench.KNOWN_STAGES)}, "
        f"known-but-never-called {set(bench.KNOWN_STAGES) - called}"
    )


def test_sig_preserves_small_rates():
    assert bench._sig(0.0021234) == 0.00212
    assert bench._sig(None) is None
    assert bench._sig(0) == 0
    assert bench._sig(123456.0) == 123456.0  # never truncates above the decimal


# ---- round-5 deadline-proofing: incremental snapshots + outer deadline ----


def _fresh_result():
    return {"metric": "m", "value": None, "unit": "u", "vs_baseline": None,
            "platform": None, "error": None, "extra": {}}


def test_emit_snapshot_stdout_and_side_file(capsys, _snapshot_tmp):
    """Every emission is a complete parseable JSON line on stdout AND an
    atomically-replaced side file; partial lines carry the marker, the
    final line does not."""
    import json

    result = _fresh_result()
    result["value"] = 1.0
    bench._emit_snapshot(result)
    result["value"] = 2.0
    bench._emit_snapshot(result, final=True)

    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 2
    first, last = json.loads(lines[0]), json.loads(lines[-1])
    assert first["value"] == 1.0 and "partial" in first
    assert first["partial"]["at"]  # names where the run was
    assert last["value"] == 2.0 and "partial" not in last
    # side file holds the newest state, no tmp litter left behind
    on_disk = json.loads(_snapshot_tmp.read_text())
    assert on_disk["value"] == 2.0
    assert not list(_snapshot_tmp.parent.glob("*.tmp.*"))


def test_run_stage_emits_snapshot_after_success_and_failure(capsys):
    """A kill at ANY moment between stages leaves the newest accumulated
    state as the last parseable stdout line."""
    import json

    result = _fresh_result()

    def ok():
        result["value"] = 42.0
        return "ok"

    assert bench._run_stage(result, "s1", ok) == "ok"

    def bad():
        raise RuntimeError("boom")

    bench._run_stage(result, "s2", bad, retry_delay=0.0)
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) >= 2
    last = json.loads(lines[-1])
    assert last["value"] == 42.0          # s1's number survived s2's failure
    assert "s2" in (last["error"] or "")  # s2's failure is in the snapshot


def test_run_stage_deadline_skip(monkeypatch, capsys):
    """Stages that would start with too little runway are skipped with an
    honest note (clean rc=0 finish beats a SIGKILL mid-stage)."""
    import json

    monkeypatch.setenv("BENCH_DEADLINE_S", "1000")
    monkeypatch.setattr(bench, "_T0", 0.0)
    monkeypatch.setattr(bench.time, "perf_counter", lambda: 970.0)
    result = _fresh_result()
    ran = []
    assert bench._run_stage(result, "late", lambda: ran.append(1)) is None
    assert ran == []
    assert result["extra"]["stages_deadline_skipped"] == ["late"]
    assert result["error"] is None
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last["extra"]["stages_deadline_skipped"] == ["late"]
