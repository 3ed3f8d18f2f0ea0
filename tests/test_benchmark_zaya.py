"""The ``zaya1_8b`` configuration and its cell ``zaya1_train_t192`` through
the benchmark's own entry point on the CPU: the tiny cell of
``benchmark/tests/tiny_zaya/`` rehearsed by ``run.py --rehearse`` (a workload
of its own, ``benchmark_out/tiny_zaya_train``: it shares no output directory
with the other files that run ``run.py``, and no xdist worker with the
load-sensitive cases of tests/test_benchmark_rehearsals.py), the three new
readers on the change and on a program without their scope or counter, the
configuration's keys, ``flops/zaya.py``'s count, and where PR 48's entries
stand in ``BENCHMARK.json``.

``benchmark/tests/test_granite_rehearsal.py``'s
``test_the_entries_are_appended_and_nothing_else_moved`` held PR 44's
entries to be the last of their lists, which PR 48's appended entries end: a
``model_config`` PR may not edit a file the benchmark has, so
tests/test_benchmark_granite.py drops it and the case below asks what it
meant (PERF.md section 7 leaves the edit to a ``benchmark`` PR)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.tests import test_rehearsal as rehearsal
from test_setup_readers import before_pr55
from benchmark.tests.test_granite_rehearsal import APPENDED as ACTOR_APPENDED
from benchmark.tests.test_granite_rehearsal import NEW_READERS as ACTOR_READERS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(BENCH, "tests", "tiny_zaya")
CELLS = rehearsal._load(os.path.join(TINY, "workloads"))
CONFIGS = rehearsal._load(os.path.join(TINY, "configs"))
CONFIG, CELL, ACTOR = "zaya1_8b", "zaya1_train_t192", "granite_actor_b32"
# PR 52's configuration, cell and four metrics: the last of their lists now
LATER_CONFIG, LATER_CELL, LATER_METRICS = "kanana_2_30b_a3b", "kanana2_train_t192", 4
NEW_READERS = ("cca_mix_step_share", "cca_mix_roofline", "router_gate_mean")
# the accepted metrics the cell joined, each list's last name
APPENDED = ("trained_steps_per_s", "setup_compile_s", "train_step_device_ms", "train_mfu",
            "train_roofline_share", "device_idle_share", "attn_step_share", "route_step_share",
            "experts_roofline", "expert_rows_max_over_mean")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of the tiny cell: the real runners, readers,
    references and flops functions, and a BENCHMARK.json that hands every
    metric of the cells of its runner to it."""
    path = tmp_path_factory.mktemp("zaya_root")
    for part in ("runners", "layer_metrics", "reference", "flops", "configs"):
        shutil.copytree(os.path.join(BENCH, part), path / part)
    for name, config in CONFIGS.items():
        shutil.copy(os.path.join(TINY, "configs", name + ".json"), path / "configs")
        shutil.copy(os.path.join(BENCH, "reference", config["reference"] + ".py"),
                    path / "reference" / (name + ".py"))
    shutil.copytree(os.path.join(TINY, "workloads"), path / "workloads")
    (path / "BENCHMARK.json").write_text(json.dumps(rehearsal._spec(tiny=CELLS)))
    return str(path)


def _run(root, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
           "--workload", "tiny_zaya_train", "--seed", "2971215073",
           "--seconds", str(CELLS["tiny_zaya_train"]["rehearse_seconds"]), "--trace", str(trace),
           "--rehearse"]
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_cpu(root, trace):
    proc = _run(root, trace)
    assert proc.returncode == 4, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last, earlier = json.loads(lines[-1]), json.loads(lines[-2])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    # the choices were handed over: a forced, a free and a float32 comparison
    compared = last["compared"]
    assert {"policy", "value", "return", "choices_agreement", "f32_policy"} <= set(compared)
    agreement, floor = compared.pop("choices_agreement")
    assert agreement >= floor
    assert all(number <= limit for number, limit in compared.values()), compared
    checks = earlier["checks"]
    assert checks.pop("device_is_tpu") is False
    checks.pop("device_ran", None)       # a CPU trace has no device plane
    assert all(checks.values()), (checks, earlier["notes"])
    assert checks["matches_reference"] and checks["choices_agree"] \
        and checks["matches_reference_f32"] and checks["no_compile_in_window"]
    # what the step counted reached the run: three routed layers of top-1
    counters = earlier["counters"]
    assert counters["counter_rows_held"] > 0 and counters["counter_expert_passes"] == 0
    assert counters["counter_expert_rows_max"] >= counters["counter_expert_rows_mean"] > 0
    assert 1 / 8 < counters["counter_router_gate_mean"] < 1
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(CELLS["tiny_zaya_train"]["answers"]["traced" if trace else "untraced"])
    # no device plane, no scope: the scope readers leave their metrics out
    assert not answered & {"cca_mix_step_share", "cca_mix_roofline", "experts_roofline",
                           "route_step_share", "attn_step_share"}
    assert earlier["counters"]["updates"] > 0


# -- the readers, on a made-up run ---------------------------------------------


class _Fake:
    """What a reader asks of a ``Run``: the train program's and a scope's
    device seconds, the counters, the work by scope, the peaks."""

    def __init__(self, scopes, counters, root=BENCH):
        self._scopes, self.counters, self.notes, self._root = scopes, counters, {}, root

    def path(self, *parts):
        return os.path.join(self._root, *parts)

    def scope(self, name):
        return self._scopes.get(name)

    def program(self, role):
        return {"seconds": 2.0, "runs": 20.0} if role == "train" else None

    def scope_work(self):
        return {"cca_mix": {"flops": 197e12 * 1e-3, "bytes": 819e9 * 3e-3}}

    def peaks(self):
        return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(name, run):
    return harness.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read(run)


def test_the_new_readers_answer_on_the_change_by_hand():
    """0.3 s under ``cca_mix`` of the program's 2 s: 15%; the scope's bytes
    bound it at 3 ms an update, 20 updates: 60 ms of the 300: 20%; the gate
    is the counter's mean."""
    run = _Fake({"cca_mix": {"seconds": 0.3, "ops": 40}}, {"counter_router_gate_mean": 0.31})
    assert _read("cca_mix_step_share", run) == pytest.approx(15.0)
    assert run.notes["cca_mix_ms_per_step"] == pytest.approx(15.0)
    assert _read("cca_mix_roofline", run) == pytest.approx(20.0)
    assert _read("router_gate_mean", run) == 0.31


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_answers_none_without_its_scope_or_counter(name, monkeypatch):
    """The parent's program: no ``cca_mix`` scope in its trace, no constant
    beside one in ``models/hybrid.py``, no ``counter_router_gate_mean``: the
    reader leaves its metric out and does not raise."""
    from handyrl_tpu.models import hybrid

    assert _read(name, _Fake({}, {})) is None
    monkeypatch.delattr(hybrid, "CCA_SCOPE")
    assert _read(name, _Fake({"cca_mix": {"seconds": 0.3, "ops": 40}}, {})) is None


def test_each_cell_is_handed_the_new_metrics_it_lists():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for cell in cells:
        made = harness.Run(BENCH, cell, seed=1, seconds=30, trace=True, rehearse=True,
                           t_process=0.0)
        assert (set(NEW_READERS) <= set(made.metric_names("per_layer"))) == (cell == CELL)
        assert bool(set(NEW_READERS) & set(made.metric_names("per_layer"))) == (cell == CELL)


# -- the configuration, the count, the entries ----------------------------------


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return next(row for row in map(json.loads, f) if row["name"] == "ZAYA1-8B")


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog's ``config`` is in the file under its own
    name, unchanged but for the cuts ``reduced`` lists (the depth and with it
    the layer types, the experts held, the vocabulary), in the file and in
    BENCHMARK.json alike; ``net_args`` runs them; and the file says what it
    assumed and what it left out."""
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/zaya1_8b.json"
    assert config["published"] == {"num_hidden_layers": 40, "layer_types": ["hybrid"] * 40,
                                   "num_experts": 16, "vocab_size": 262272}
    net = config["env_args"]["net_args"]
    assert net["pattern"] == "CE" * config["num_hidden_layers"] == "CE" * 5
    assert config["layer_types"] == ["hybrid"] * 5 and config["num_experts"] == net["experts_held"] == 8
    assert (net["d_model"], net["n_heads"], net["n_kv_heads"], net["head_dim"]) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"]) == (2048, 8, 2, 128)
    assert (net["cca_time0"], net["cca_time1"], net["rotary_factor"], net["rope_theta"]) == (
        config["cca_time0"], config["cca_time1"], config["partial_rotary_factor"],
        config["rope_parameters"]["hybrid"]["rope_theta"]) == (2, 2, 0.5, 5e6)
    assert (net["n_experts"], net["top_k"], net["expert_width"], net["router_width"]) == (
        config["published"]["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"], config["router_hidden_size"]) == (16, 1, 2048, 256)
    assert net["norm_eps"] == config["rms_norm_eps"] == 1e-5 and net["shared_width"] == 0
    assert net["gated_experts"] and config["hidden_act"] == "silu" and net["router"] == "mlp"
    assert config["module"] == "HybridNet" and config["flops"] == "zaya"
    assert len(config["assumed"]) >= 8 and "2 chips" in config["deployment"]
    assert any("skip choice" in line and "residual" in line for line in config["departures"])
    for limit in ("reference_tolerance", "choices_agreement_floor", "reference_tolerance_f32"):
        assert config[limit] > 0 and len(config[limit + "_why"]) > 200
    catalog = _catalog()
    if catalog is not None:
        assert config["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


def test_the_count_is_of_tokens_only():
    """``flops/zaya.py``: twice the observed share, twice the operations; no
    term for padding, replay or an empty buffer slot; an expert term that
    follows the experts held; by hand at the published widths a token's
    forward is 11.2 MFLOP of CCA and 13.9 of router and held experts a
    layer."""
    flops = harness.load_module(os.path.join(BENCH, "flops", "zaya.py"))
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    work = flops.train_update(config, cell)
    assert work["parameters"] == 539_714_866
    assert work["tokens"] == pytest.approx(64 * (184 * 0.413 + 8 * 0.127))
    net = config["env_args"]["net_args"]
    cca = 2 * (2048 * 1536 + 1024 * 2048 + 2 * 1280 * 128 + 2 * 1280 + 2 * 38.996 * 1024)
    assert 2 * flops.layer_macs_per_token(net, "C", 38.996) == pytest.approx(cca)
    assert 2 * flops.layer_macs_per_token(net, "E", 0) == pytest.approx(
        2 * (2048 * 256 + 2 * 256 * 256 + 256 * 16 + 0.5 * 3 * 2048 * 2048))
    half = json.loads(json.dumps(config))
    half["shapes"].update(observed_share=0.2065, observed_share_burn_in=0.0635)
    less = flops.train_update(half, cell)
    assert less["tokens"] == pytest.approx(work["tokens"] / 2)
    assert less["flops"] < 0.51 * work["flops"]      # the keys a token sees fall too
    whole = json.loads(json.dumps(config))
    whole["env_args"]["net_args"]["experts_held"] = 16
    assert flops.scope_work(whole, cell)["experts"]["flops"] == pytest.approx(
        2 * flops.scope_work(config, cell)["experts"]["flops"])
    scoped = flops.scope_work(config, cell)
    assert set(scoped) == {"experts", "cca_mix"}
    assert scoped["experts"]["rows"] == pytest.approx(5 * 0.5 * work["tokens"])
    assert scoped["cca_mix"]["flops"] < 0.03 * work["flops"] < scoped["experts"]["flops"]


def _before(spec):
    """``spec`` as it stood before PR 52's entries, which are held to stand
    last (behind them only PR 55's four ``setup_*`` metrics, which list every
    cell: ``before_pr55``): its configuration, its cell, its metrics (each lists its cell
    alone), and its cell's name at the end of every older list it joined."""
    spec = before_pr55(spec)
    assert spec["configs"].pop()["name"] == LATER_CONFIG
    assert spec["workloads"].pop()["name"] == LATER_CELL
    for _ in range(LATER_METRICS):
        assert spec["per_layer"].pop()["workloads"] == [LATER_CELL]
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if LATER_CELL in metric.get("workloads", ()):
            assert metric["workloads"].pop() == LATER_CELL
    return spec


def test_the_entries_are_appended_and_nothing_else_moved():
    """PR 48's: one configuration, one cell and three metrics at the end of
    their lists but for PR 52's, which follow them (its configuration, its
    cell, its four metrics, and its cell's name behind this one's in every
    list both are in: ``_before``), the cell's name at the end of the lists of
    the accepted metrics whose readers answer for it, and in no other.  PR 44's (the
    benchmark's own case, restated): its configuration, its cell and its five
    metrics stand right before them, its cell's name at the end of its
    lists but for ``setup_compile_s``, where PR 48's follows."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = _before(json.load(f))
    assert [c["name"] for c in spec["configs"][-2:]] == ["granite_4_0_h_small", CONFIG]
    assert [w["name"] for w in spec["workloads"][-2:]] == [ACTOR, CELL]
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    entry = spec["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])
    assert len(entry["why"]) <= 200 and len(spec["configs"][-1]["why"]) <= 200
    assert cell["runner"] == "train_step_routed" and entry["chips"] == 1
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-8:] == list(ACTOR_READERS) + list(NEW_READERS)
    layers = {m["layer"] for m in spec["per_layer"][:-3]}
    for metric in spec["per_layer"][-3:]:
        assert metric["workloads"] == [CELL] and metric["moves"] == "trained_steps_per_s"
        assert metric["layer"] in layers
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    for metric in spec["per_layer"][-8:-3]:
        assert metric["workloads"] == [ACTOR] and metric["moves"] == "selfplay_steps_per_s"
    lists = {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer") for m in spec[g]
             if "workloads" in m}
    listed = sorted(name for name, cells in lists.items() if CELL in cells)
    assert listed == sorted(NEW_READERS + APPENDED)
    for name in listed:
        assert lists[name][-1] == CELL
    assert sorted(n for n, cells in lists.items() if ACTOR in cells) == sorted(
        ACTOR_READERS + ACTOR_APPENDED)
    for name in ACTOR_READERS + ACTOR_APPENDED:
        assert lists[name][-1] == (CELL if name == "setup_compile_s" else ACTOR)
    # the traffic is the two older T192 cells', key for key
    for other in ("nemotron_twotower_train_t192", "ouro_train_t192"):
        theirs = rehearsal._load(os.path.join(BENCH, "workloads"))[other]
        for key in ("traffic", "runner", "chips", "train_args", "mesh", "lr", "n_batches",
                    "fill_episodes", "in_flight", "programs"):
            assert cell[key] == theirs[key], (other, key)
    # a cell lists a per-layer metric only where it reports what that metric moves
    reports = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"trained_steps_per_s", "setup_s"}
    for metric in spec["per_layer"]:
        if CELL in metric["workloads"]:
            assert metric["moves"] in reports, metric["name"]
