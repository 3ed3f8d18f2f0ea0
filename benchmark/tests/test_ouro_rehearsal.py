"""The looped dense trunk end to end on the CPU through the same ``run.py``
the chip runs: a tiny ``HybridNet`` cell of sandwiched ``*-`` layers run four
times (``tiny_ouro/``) on the ``train_step_routed`` runner (``train_step``
under a second name: see that file), held to its plain reference, the
step's counters in ``run.counters``; and what ``BENCHMARK.json`` and the
configuration's file hold of ``ouro_2_6b``.

It has a directory of its own because a tiny cell of that runner in
``tiny/`` fails ``test_rehearsal.py``'s case of the unknown runner.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ouro_rehearsal.py -q
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import test_rehearsal as rehearsal  # noqa: E402
from benchmark import harness  # noqa: E402

TINY = os.path.join(HERE, "tiny_ouro")
CELLS = rehearsal._load(os.path.join(TINY, "workloads"))
CONFIGS = rehearsal._load(os.path.join(TINY, "configs"))
CELL, CONFIG = "ouro_train_t192", "ouro_2_6b"
NEW_READERS = ("mlp_roofline", "attn_step_share", "norm_step_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of the tiny looped cell: the real runners, readers,
    references and flops functions, and a BENCHMARK.json that hands every
    metric of the cells of its runner to it."""
    path = tmp_path_factory.mktemp("ouro_root")
    for part in ("runners", "layer_metrics", "reference", "flops", "configs"):
        shutil.copytree(os.path.join(BENCH, part), path / part)
    for name, config in CONFIGS.items():
        shutil.copy(os.path.join(TINY, "configs", name + ".json"), path / "configs")
        shutil.copy(os.path.join(BENCH, "reference", config["reference"] + ".py"),
                    path / "reference" / (name + ".py"))
    shutil.copytree(os.path.join(TINY, "workloads"), path / "workloads")
    (path / "BENCHMARK.json").write_text(json.dumps(rehearsal._spec(tiny=CELLS)))
    return str(path)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_looped_cell_rehearses_on_cpu(root, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
           "--workload", "tiny_ouro_train", "--seed", "2971215073",
           "--seconds", str(CELLS["tiny_ouro_train"]["rehearse_seconds"]),
           "--trace", str(trace), "--rehearse"]
    proc = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 4, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last, earlier = json.loads(lines[-1]), json.loads(lines[-2])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    # an unrouted net: one comparison, every head under its limit
    compared = last["compared"]
    assert set(compared) == {"policy", "value", "return", "failed"}
    assert all(number <= limit for number, limit in compared.values()), compared
    checks = earlier["checks"]
    assert checks.pop("device_is_tpu") is False
    checks.pop("device_ran", None)       # a CPU trace has no device plane
    assert all(checks.values()), (checks, earlier["notes"])
    assert checks["matches_reference"] and checks["no_compile_in_window"]
    # what the step counted reached the run: four passes over four sub-layers,
    # a gate that lets some of every token go and keeps some
    counters = earlier["counters"]
    assert counters["counter_layer_applications"] == 16
    assert 0.0 < counters["counter_exit_mass_last"] < 1.0
    assert counters["counter_observed_steps"] <= counters["counter_packed_slots"]
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(CELLS["tiny_ouro_train"]["answers"]["traced" if trace else "untraced"])
    # no device plane, no scope: the scope readers leave their metrics out
    assert not answered & set(NEW_READERS)


def test_the_entries_are_appended_and_nothing_else_moved():
    """One configuration, one cell, three metrics at the end of their lists;
    the cell's name at the end of ``trained_steps_per_s``'s ``workloads`` and
    just before the routed cell's in the five lists that
    ``test_hybrid_rehearsal.py`` holds that cell to end."""
    spec = _benchmark()
    assert spec["configs"][-1]["name"] == CONFIG and spec["workloads"][-1]["name"] == CELL
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    entry = spec["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])
    assert len(entry["why"]) <= 200 and cell["runner"] == "train_step_routed"
    assert [m["name"] for m in spec["per_layer"][-3:]] == list(NEW_READERS)
    layers = {m["layer"] for m in spec["per_layer"][:-3]}
    for metric in spec["per_layer"][-3:]:
        assert metric["workloads"] == [CELL] and metric["moves"] == "trained_steps_per_s"
        assert metric["layer"] in layers and metric["source"] == "device_trace"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    lists = {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer") for m in spec[g]
             if "workloads" in m}
    listed = sorted(name for name, cells in lists.items() if CELL in cells)
    assert listed == sorted(NEW_READERS + (
        "trained_steps_per_s", "setup_compile_s", "train_step_device_ms", "train_mfu",
        "train_roofline_share", "device_idle_share"))
    for name in listed:
        ends = lists[name][-1] == "nemotron_twotower_train_t192"
        assert lists[name][-2 if ends else -1] == CELL
        assert ends == (name not in NEW_READERS + ("trained_steps_per_s",))


def test_the_cell_runs_the_traffic_of_the_nemotron_cell_key_for_key():
    cells = rehearsal._load(os.path.join(BENCH, "workloads"))
    ours, theirs = cells[CELL], cells["nemotron_twotower_train_t192"]
    for key in ("traffic", "train_args", "mesh", "lr", "n_batches", "fill_episodes",
                "in_flight", "programs", "runner", "chips"):
        assert ours[key] == theirs[key], key
    configs = rehearsal._load(os.path.join(BENCH, "configs"))
    shapes = configs[CONFIG]["shapes"]
    for key, value in configs["nemotron_twotower_30b_a3b"]["shapes"].items():
        if not key.endswith("_why"):
            assert shapes[key] == value, key


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` is in the file under its own
    key, but for the cuts ``reduced`` lists (the depth, with it the list of
    layer types; the vocabulary), in the file and in BENCHMARK.json alike;
    and ``net_args`` runs them."""
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    entry = next(c for c in _benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/ouro_2_6b.json" and len(entry["source"]) <= 200
    net = config["env_args"]["net_args"]
    assert net["pattern"] == "*-" * config["num_hidden_layers"] == "*-" * 8
    assert config["layer_types"] == ["full_attention"] * 8
    assert config["published"]["num_hidden_layers"] == 48
    same = {
        "hidden_size": "d_model", "intermediate_size": "mlp_width", "head_dim": "head_dim",
        "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "total_ut_steps": "loops",
    }
    for published, run_as in same.items():
        assert config[published] == net[run_as], published
    assert net["sandwich"] is True and config["use_sliding_window"] is False
    assert net["out_scale_init"] == len(net["pattern"]) ** -0.5 == 0.25     # `assumed` says why
    assert config["early_exit_threshold"] == 1 and config["hidden_act"] == "silu"
    assert (config["module"], config["flops"]) == ("HybridNet", "ouro")
    for key in ("source", "assumed", "departures", "deployment", "published",
                "reference_tolerance", "reference_tolerance_why"):
        assert config[key], key
    if os.path.exists(CATALOG):     # the catalog the configuration was drawn from
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


@pytest.mark.parametrize("cell", ["geese_loop", "xfmr_train_t64", "xfmr_train_t64_dp4",
                                  "nemotron_twotower_train_t192", CELL])
def test_only_the_looped_cell_is_handed_the_new_metrics(cell):
    made = harness.Run(BENCH, cell, seed=1, seconds=30, trace=True, rehearse=True, t_process=0.0)
    names = set(made.metric_names("per_layer"))
    assert (set(NEW_READERS) <= names) == (cell == CELL)
    assert not (set(NEW_READERS) & names) or cell == CELL
    if cell == CELL:    # what its traced run must answer
        assert names == set(NEW_READERS) | {
            "setup_compile_s", "train_step_device_ms", "train_mfu", "train_roofline_share",
            "device_idle_share"}
        assert set(made.metric_names("end_to_end")) == {"trained_steps_per_s", "setup_s"}


# ---------------------------------------------------------------------------
# the three readers, by hand on a reduced trace
# ---------------------------------------------------------------------------


def _traced(scopes):
    """A traced run of the cell: 2 s of ``jit__step``, 2.5 runs of 0.8 s."""
    made = harness.Run(BENCH, CELL, seed=1, seconds=30, trace=True, rehearse=True, t_process=0.0)
    made.reduced = {"window_s": 2.0, "scopes": scopes, "programs": {
        "jit__step(77)": {"seconds": 2.0, "runs": 2.5, "whole_seconds": 1.6, "whole_runs": 2.0}}}
    return made


def _read(run, name):
    return harness.load_module(run.path("layer_metrics", name + ".py")).read(run)


def test_the_three_readers_by_hand():
    run = _traced({"attn": {"seconds": 0.7, "ops": 9.0}, "rope": {"seconds": 0.05, "ops": 2.0},
                   "gqa": {"seconds": 0.1, "ops": 3.0}, "mlp": {"seconds": 1.0, "ops": 5.0},
                   "norm": {"seconds": 0.12, "ops": 4.0}})
    assert _read(run, "attn_step_share") == pytest.approx(35.0)        # 0.7 / 2.0
    assert run.notes["attn_ms_per_step"] == pytest.approx(
        {"attn": 280.0, "rope": 20.0, "gqa": 40.0})                      # over 2.5 runs
    assert _read(run, "norm_step_share") == pytest.approx(6.0)         # 0.12 / 2.0
    # the MLPs' required work at the peak, 2.5 runs of it, over the second under the scope
    work = run.scope_work()["mlp"]
    peaks = run.peaks()
    least = max(work["flops"] / peaks["bf16_flops_per_s"], work["bytes"] / peaks["hbm_bytes_per_s"])
    assert 0.15 < least < 0.18                                          # 32.3 TFLOP at 197 TFLOP/s
    assert _read(run, "mlp_roofline") == pytest.approx(100.0 * least * 2.5 / 1.0)


@pytest.mark.parametrize("name", NEW_READERS + ("dense_trunk",))
def test_a_reader_answers_none_without_its_scope_or_its_constant(name, monkeypatch):
    """A run whose trace carries none of the scopes (the nemotron tiny cell
    is handed these metrics too), an untraced run, and a program that has no
    such constant (the parent's): nothing to read, nothing raised, no note."""
    from handyrl_tpu.models import hybrid

    for run in (_traced({"ssd": {"seconds": 0.3, "ops": 2.0}}), _traced({})):
        assert _read(run, name) is None and run.notes == {}
    run = _traced({})
    run.reduced = None
    assert _read(run, name) is None and run.notes == {}
    run = _traced({"attn": {"seconds": 0.7, "ops": 9.0}, "mlp": {"seconds": 1.0, "ops": 5.0},
                   "norm": {"seconds": 0.12, "ops": 4.0}})
    for constant in ("ATTN_SCOPE", "ROPE_SCOPE", "GQA_SCOPE", "MLP_SCOPE", "NORM_SCOPE"):
        monkeypatch.delattr(hybrid, constant)
    assert _read(run, name) is None and run.notes == {}
