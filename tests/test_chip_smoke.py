"""The seams chip_smoke.py and the entry points rest on, checked without a
chip: where the compile cache goes, that the smoke refuses anything but a
TPU, and that a failed phase or a fired fallback can never end in
``"ok": true``.  The phases themselves run on the chip
(``python chip_smoke.py``); their CPU rehearsal at tiny sizes is the
slow-marked test at the bottom.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

REPO = Path(__file__).resolve().parents[1]

import chip_smoke
from handyrl_tpu.utils import compile_cache


# -- compile cache placed from outside ----------------------------------------


class _FakeJax:
    """Stands in for the jax module inside the helper: records config
    updates instead of applying them to this process's real jax."""

    def __init__(self, platforms=""):
        self.updates = {}
        self.config = SimpleNamespace(
            jax_platforms=platforms,
            update=lambda name, value: self.updates.__setitem__(name, value),
        )


def _cache_path_updates(fake):
    return {k: v for k, v in fake.updates.items() if k.endswith("cache_dir")}


def test_cache_env_set_uses_that_directory_and_sets_no_other(monkeypatch, tmp_path):
    fake = _FakeJax()
    monkeypatch.setattr(compile_cache, "jax", fake)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "placed")
    assert _cache_path_updates(fake) == {}, "a cache path was set in code"
    # the thresholds still drop, so small programs are cached there too
    assert fake.updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert fake.updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_cache_env_unset_uses_one_fixed_path_in_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for cwd in (tmp_path, REPO / "tests"):
        fake = _FakeJax()
        monkeypatch.setattr(compile_cache, "jax", fake)
        monkeypatch.chdir(cwd)
        paths.append(compile_cache.enable_compile_cache())
        assert list(_cache_path_updates(fake).values()) == [paths[-1]]
    assert paths[0] == paths[1] == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_cache_stays_off_by_default_for_a_cpu_pinned_process(monkeypatch):
    """XLA:CPU logs a machine-feature error on every cache hit; a test or
    tool child gets the cache only where the variable asks for it."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fake = _FakeJax(platforms="cpu")
    monkeypatch.setattr(compile_cache, "jax", fake)
    assert compile_cache.enable_compile_cache() is None
    assert fake.updates == {}


def test_only_the_helper_names_a_cache_path():
    needle = "compilation_cache" + "_dir"
    sources = list(REPO.glob("*.py"))
    for root in ("handyrl_tpu", "tests", "tools", "scripts", "examples"):
        sources += (REPO / root).rglob("*.py")
    hits = [
        str(p.relative_to(REPO)) for p in sources
        if needle in p.read_text(errors="replace")
    ]
    assert hits == ["handyrl_tpu/utils/compile_cache.py"]


# -- the smoke refuses anything but the chip ----------------------------------


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_on_cpu_exits_nonzero_and_prints_no_result(tmp_path):
    proc = _run_smoke(tmp_path, REPO / "chip_smoke.py")
    assert proc.returncode not in (0, None)
    assert '"ok"' not in proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_chip_smoke_alone_without_the_program_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    proc = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert proc.returncode not in (0, None)
    assert '"ok"' not in proc.stdout


# -- a failed phase or a fired fallback never prints "ok": true ----------------


@pytest.fixture
def _fake_chip(monkeypatch, tmp_path):
    import jax

    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path / "out"))
    monkeypatch.setattr(chip_smoke, "ON_CHIP", False)  # main() sets it
    for name in ("phase_train_host", "phase_transformer", "phase_serve"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: {"stub": True})
    monkeypatch.setattr(
        chip_smoke, "phase_train_device",
        lambda *a, **k: {"model_dir": "m", "epoch_written": 1},
    )


def test_all_phases_ok_ends_with_exactly_the_contract_line(_fake_chip, capsys):
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert [l.split(":")[0] for l in lines[1:-1]] == [
        "phase train-host", "phase train-device", "phase transformer", "phase serve",
    ]
    assert sum('"ok": true' in l for l in lines) == 1


def test_a_failed_phase_exits_nonzero_and_never_prints_ok(
    _fake_chip, monkeypatch, capsys
):
    def degraded(*a, **k):
        raise AssertionError("pipe_batcher_fallback != 0")

    monkeypatch.setattr(chip_smoke, "phase_train_device", degraded)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "phase train-device: FAILED" in out
    # the phase that needs the failed one's checkpoint fails with it; the
    # independent ones still ran and reported
    assert "phase serve: FAILED" in out and "phase transformer: ok" in out


def test_wrong_chip_count_is_refused(_fake_chip, capsys):
    assert chip_smoke.main(["--multichip"]) == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("line", [
    "[handyrl_tpu] shm batch pipeline degrading to threaded batchers: x",
    "falling back to the shm assembly plane",
    "device generation stops (training continues",
    "Traceback (most recent call last):",
])
def test_fallback_lines_in_a_phase_log_fail_it(line):
    with pytest.raises(AssertionError, match="marker"):
        chip_smoke._assert_no_fallback("epoch 1\n" + line + "\nepoch 2\n")
    chip_smoke._assert_no_fallback("epoch 1\nloss = total:0.1\n")


def test_training_checks_catch_what_a_fallback_would_hide(monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)
    os.makedirs("models")
    Path("models/latest.ckpt").write_bytes(b"x")
    good = [{"epoch": 0, "steps": 0},
            {"epoch": 1, "steps": 9, "loss": {"total": 0.1}, "mfu": 0.01}]
    monkeypatch.setattr(chip_smoke, "ON_CHIP", True)
    chip_smoke._check_training(good)
    no_mfu = [dict(r) for r in good]
    del no_mfu[1]["mfu"]
    with pytest.raises(AssertionError, match="mfu"):
        chip_smoke._check_training(no_mfu)       # unknown device kind / no trace
    with pytest.raises(AssertionError, match="non-finite"):
        chip_smoke._check_training(
            [good[0], dict(good[1], loss={"total": float("nan")})]
        )
    with pytest.raises(AssertionError, match="no SGD update"):
        chip_smoke._check_training([good[0], dict(good[1], steps=0)])


# -- the transformer phase is the benchmark's cell ------------------------------


def test_transformer_sizes_equal_the_benchmarks_cell():
    """The smoke's transformer phase and the ``xfmr_train_t64`` cell are one
    model under one step: a change to either side alone fails here."""
    config = json.loads((REPO / "benchmark/configs/xfmr_d1536.json").read_text())
    cell = json.loads((REPO / "benchmark/workloads/xfmr_train_t64.json").read_text())
    assert cell["config"] == config["name"]
    assert chip_smoke.TRANSFORMER_TPU_NET_ARGS == config["env_args"]["net_args"]
    assert chip_smoke.TRANSFORMER_TPU_OVERRIDES == {**config["train_args"], **cell["train_args"]}
    long = chip_smoke.TRANSFORMER_LONG_TPU
    assert long["net_args"] == config["env_args"]["net_args"]
    assert long["compute_dtype"] == config["train_args"]["compute_dtype"]
    assert long["batch_by_t"][long["sweep_t"][0]] == cell["train_args"]["batch_size"]
    assert chip_smoke.SIZES["ring"] == {"shape": (2, 1024, 16, 96), "window": 32}


# -- rehearsal 1: every phase end to end on the CPU at a tiny size -------------

TINY = {
    "train_host": {"epochs": 2, "update_episodes": 40, "minimum_episodes": 40},
    "train_device": {
        "batch_size": 8, "forward_steps": 8, "device_rollout_games": 8,
        "device_replay_k_steps": 16, "device_replay_slots": 256,
        "fused_steps": 2, "device_eval_games": 8,
        "epochs": 2, "update_episodes": 40, "minimum_episodes": 10,
    },
    "transformer": {
        "net_args": {"d_model": 64, "n_heads": 2, "n_layers": 2, "memory_len": 16},
        "overrides": {"batch_size": 4, "burn_in_steps": 2, "forward_steps": 14,
                      "observation": True, "compute_dtype": "bfloat16"},
        "steps": 2,
    },
    "serve": {"games": 1, "max_steps": 4},
    "dp": {"batch_size": 8, "device_rollout_games": 8, "device_replay_k_steps": 8,
           "device_replay_slots": 64, "fused_steps": 2, "dispatches": 8},
    "ring": {"shape": (2, 32, 2, 8), "window": 8},
}


@pytest.mark.slow
def test_every_phase_rehearses_on_the_cpu_at_tiny_size(monkeypatch, tmp_path):
    """What to run before a chip call after touching chip_smoke.py or a
    path it drives: wrong paths, arguments and control flow show here;
    the chip-only asserts (mfu, compiled kernel) stay off."""
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "ON_CHIP", False)
    one_chip = {
        "train-host": lambda r: chip_smoke.phase_train_host(TINY["train_host"]),
        "train-device": lambda r: chip_smoke.phase_train_device(TINY["train_device"]),
        "transformer": lambda r: chip_smoke.phase_transformer(TINY["transformer"], 0),
        "serve": lambda r: chip_smoke.phase_serve(
            TINY["serve"], r["train-device"]["model_dir"],
            r["train-device"]["epoch_written"], 0,
        ),
    }
    four_chips = {
        "dp-train-step": lambda r: chip_smoke.phase_dp_train_step(TINY["dp"]),
        "dp-section-ring": lambda r: chip_smoke.phase_dp_train_step(
            TINY["dp"], chip_smoke._sections_setup
        ),
        "dp-rollout-replay": lambda r: chip_smoke.phase_dp_rollout_replay(TINY["dp"]),
        "ring-attention": lambda r: chip_smoke.phase_ring_attention(TINY["ring"], 0),
    }
    assert chip_smoke.run_phases({**one_chip, **four_chips}) == []
