"""The ``kanana_2_30b_a3b`` configuration and its cell ``kanana2_train_t192``
through the benchmark's own entry point on the CPU: the tiny cell of
``benchmark/tests/tiny_kanana/`` rehearsed by ``run.py --rehearse`` (a
workload of its own, ``benchmark_out/tiny_kanana_train``: it shares no output
directory with the other files that run ``run.py``, and no xdist worker with
the load-sensitive cases of tests/test_benchmark_rehearsals.py), the four new
readers on the change and on a program without their scopes or counters, the
configuration's keys, ``flops/kanana.py``'s count, and where PR 52's entries
stand in ``BENCHMARK.json``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.tests import test_rehearsal as rehearsal
from test_setup_readers import before_pr55

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(BENCH, "tests", "tiny_kanana")
CELLS = rehearsal._load(os.path.join(TINY, "workloads"))
CONFIGS = rehearsal._load(os.path.join(TINY, "configs"))
CONFIG, CELL, BEFORE = "kanana_2_30b_a3b", "kanana2_train_t192", "zaya1_train_t192"
NEW_READERS = ("mla_core_step_share", "mla_proj_roofline", "mla_core_roofline",
               "latent_state_share")
# the accepted metrics the cell joined, each list's last name
APPENDED = ("trained_steps_per_s", "setup_compile_s", "train_step_device_ms", "train_mfu",
            "train_roofline_share", "device_idle_share", "attn_step_share", "route_step_share",
            "experts_roofline", "expert_rows_max_over_mean")
# the routed cells' metrics it stays out of: their entries are compared whole
# by the benchmark's own tests (test_expert_buffer_fill.py, test_phase_readers.py)
LEFT = ("expert_buffer_fill", "update_step_share", "packed_padding_share")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of the tiny cell: the real runners, readers,
    references and flops functions, and a BENCHMARK.json that hands every
    metric of the cells of its runner to it."""
    path = tmp_path_factory.mktemp("kanana_root")
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
           "--workload", "tiny_kanana_train", "--seed", "2971215073",
           "--seconds", str(CELLS["tiny_kanana_train"]["rehearse_seconds"]), "--trace", str(trace),
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
    # what the step counted reached the run: two routed layers of top-3, three
    # ``L`` layers' latents of 12 + 4 against 4 heads of 8 + 4 + 6
    counters = earlier["counters"]
    assert counters["counter_rows_held"] > 0 and counters["counter_expert_passes"] == 0
    assert counters["counter_expert_rows_max"] >= counters["counter_expert_rows_mean"] > 0
    assert counters["counter_latent_state_values"] > 0
    assert counters["counter_latent_state_values"] * 72 == pytest.approx(
        counters["counter_expanded_state_values"] * 16)
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(
        CELLS["tiny_kanana_train"]["answers"]["traced" if trace else "untraced"])
    # no device plane, no scope: the scope readers leave their metrics out
    assert not answered & {"mla_core_step_share", "mla_proj_roofline", "mla_core_roofline",
                           "experts_roofline", "route_step_share", "attn_step_share"}
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
        return {"mla_proj": {"flops": 197e12 * 5e-3, "bytes": 819e9 * 1e-3},
                "mla_core": {"flops": 197e12 * 1e-3, "bytes": 819e9 * 3e-3}}

    def peaks(self):
        return {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


SCOPES = {"mla_proj": {"seconds": 0.5, "ops": 90}, "mla_core": {"seconds": 0.3, "ops": 40}}
COUNTERS = {"counter_latent_state_values": 576.0, "counter_expanded_state_values": 10240.0}


def _read(name, run):
    return harness.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read(run)


def test_the_new_readers_answer_on_the_change_by_hand():
    """0.3 s under ``mla_core`` of the program's 2 s: 15%, 15 ms a step beside
    ``mla_proj``'s 25; the core's bytes bound it at 3 ms an update, 20
    updates: 60 ms of the 300: 20%; the projections' operations bound them at
    5 ms: 100 ms of the 500: 20%; 576 values kept of 10,240: 5.625%."""
    run = _Fake(SCOPES, COUNTERS)
    assert _read("mla_core_step_share", run) == pytest.approx(15.0)
    assert run.notes["mla_ms_per_step"] == {"mla_core": pytest.approx(15.0),
                                            "mla_proj": pytest.approx(25.0)}
    assert _read("mla_core_roofline", run) == pytest.approx(20.0)
    assert _read("mla_proj_roofline", run) == pytest.approx(20.0)
    assert _read("latent_state_share", run) == pytest.approx(5.625)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_answers_none_without_its_scope_or_counter(name, monkeypatch):
    """The parent's program: no ``mla_*`` scope in its trace, no constant
    beside one in ``models/hybrid.py``, neither counter (or nothing handed on:
    a window without burn-in): the reader leaves its metric out and does not
    raise."""
    from handyrl_tpu.models import hybrid

    assert _read(name, _Fake({}, {})) is None
    assert _read(name, _Fake({}, {"counter_latent_state_values": 0.0,
                                  "counter_expanded_state_values": 0.0})) is None
    monkeypatch.delattr(hybrid, "MLA_PROJ_SCOPE")
    monkeypatch.delattr(hybrid, "MLA_CORE_SCOPE")
    assert _read(name, _Fake(SCOPES, {})) is None


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
        return next(row for row in map(json.loads, f)
                    if row["name"] == "kanana-2-30b-a3b-instruct-2601")


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog's ``config`` is in the file under its own
    name, unchanged but for the cuts ``reduced`` lists (the depth, the experts
    held, the vocabulary; not the leading dense layer), in the file and in
    BENCHMARK.json alike; ``net_args`` runs them; and the file says what it
    assumed, what it left out and what deployment it stands for."""
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] + " model_type deepseek_v3"
    assert entry["file"] == "benchmark/configs/kanana_2_30b_a3b.json"
    assert config["published"] == {"num_hidden_layers": 48, "n_routed_experts": 128,
                                   "vocab_size": 128256}
    net = config["env_args"]["net_args"]
    assert net["pattern"] == "L-" + "LE" * 4 and config["num_hidden_layers"] == 5
    assert config["first_k_dense_replace"] == net["pattern"].count("-") == 1
    assert config["n_routed_experts"] == net["experts_held"] == 16
    assert (net["d_model"], net["n_heads"], net["qk_nope_dim"], net["qk_rope_dim"],
            net["v_head_dim"], net["kv_latent"]) == (
        config["hidden_size"], config["num_attention_heads"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"], config["kv_lora_rank"]) == (
        2048, 32, 128, 64, 128, 512)
    assert config["q_lora_rank"] is None and config["rope_interleave"] is True
    assert config["qk_head_dim"] == net["qk_nope_dim"] + net["qk_rope_dim"] == 192
    assert (net["mlp_width"], net["expert_width"], net["shared_width"]) == (
        config["intermediate_size"], config["moe_intermediate_size"],
        config["n_shared_experts"] * config["moe_intermediate_size"]) == (6144, 768, 1536)
    assert (net["n_experts"], net["top_k"], net["routed_scale"], net["rope_theta"]) == (
        config["published"]["n_routed_experts"], config["num_experts_per_tok"],
        config["routed_scaling_factor"], config["rope_theta"]) == (128, 6, 2.448, 1e6)
    assert net["norm_eps"] == config["rms_norm_eps"] == 1e-6
    assert net["router"] == config["scoring_func"] == "sigmoid" and config["norm_topk_prob"]
    assert net["gated_experts"] and config["hidden_act"] == "silu"
    assert config["n_group"] == config["topk_group"] == 1
    assert config["module"] == "HybridNet" and config["flops"] == "kanana"
    assert len(config["assumed"]) >= 4 and "8 chips" in config["deployment"]
    assert any("cross-entropy" in line for line in config["departures"])
    for limit in ("reference_tolerance", "choices_agreement_floor", "reference_tolerance_f32"):
        assert config[limit] > 0 and len(config[limit + "_why"]) > 200
    catalog = _catalog()
    if catalog is not None:
        assert config["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key


def test_the_count_is_of_tokens_only():
    """``flops/kanana.py``: twice the observed share, twice the operations; no
    term for padding, replay, an empty buffer slot or a second expansion of the
    burn-in latents; an expert term that follows the experts held; by hand at
    the published widths a token's forward is 26.35M multiply-adds of
    projections a latent attention layer, 37.75M the dense layer, 13.24M an
    expert layer."""
    flops = harness.load_module(os.path.join(BENCH, "flops", "kanana.py"))
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    work = flops.train_update(config, cell)
    assert work["parameters"] == 515_482_840
    assert work["tokens"] == pytest.approx(64 * (184 * 0.413 + 8 * 0.127))
    net = config["env_args"]["net_args"]
    proj = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert flops.mla_proj_macs_per_token(net) == proj == 26_345_472
    assert flops.layer_macs_per_token(net, "L", 38.996) == pytest.approx(
        proj + 38.996 * 32 * (192 + 128))
    assert flops.layer_macs_per_token(net, "-", 0) == 3 * 2048 * 6144
    assert flops.layer_macs_per_token(net, "E", 0) == pytest.approx(
        2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768)
    half = json.loads(json.dumps(config))
    half["shapes"].update(observed_share=0.2065, observed_share_burn_in=0.0635)
    less = flops.train_update(half, cell)
    assert less["tokens"] == pytest.approx(work["tokens"] / 2)
    assert less["flops"] < 0.51 * work["flops"]      # the keys a token sees fall too
    whole = json.loads(json.dumps(config))
    whole["env_args"]["net_args"]["experts_held"] = 128
    assert flops.scope_work(whole, cell)["experts"]["flops"] == pytest.approx(
        8 * flops.scope_work(config, cell)["experts"]["flops"])
    scoped = flops.scope_work(config, cell)
    assert set(scoped) == {"experts", "mla_proj", "mla_core"}
    assert scoped["experts"]["rows"] == pytest.approx(4 * 0.75 * work["tokens"])
    # the projections, not the scores, are the mixer's work: over half the update's
    assert scoped["mla_core"]["flops"] < 0.02 * scoped["mla_proj"]["flops"]
    assert 0.5 * work["flops"] < scoped["mla_proj"]["flops"] < 0.6 * work["flops"]


def test_the_entries_are_appended_and_nothing_else_moved():
    """PR 52's: one configuration, one cell and four metrics at the end of
    their lists (but for PR 55's four ``setup_*`` metrics, which follow them
    and list every cell: ``before_pr55`` holds them to that and takes them
    off), the cell's name at the end of the lists of the accepted
    metrics whose readers answer for it, and in no other: not in the three
    whose entries the benchmark's own tests compare whole."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = before_pr55(json.load(f))
    assert [c["name"] for c in spec["configs"][-2:]] == ["zaya1_8b", CONFIG]
    assert [w["name"] for w in spec["workloads"][-2:]] == [BEFORE, CELL]
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    entry = spec["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])
    assert len(entry["why"]) <= 200 and len(spec["configs"][-1]["why"]) <= 200
    assert cell["runner"] == "train_step_routed" and entry["chips"] == 1
    assert cell["scopes"] == ["attn", "mla_proj", "rope", "mla_core", "mlp", "route", "experts",
                              "norm"]
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-4:] == list(NEW_READERS)
    layers = {m["layer"] for m in spec["per_layer"][:-4]}
    for metric in spec["per_layer"][-4:]:
        assert metric["workloads"] == [CELL] and metric["moves"] == "trained_steps_per_s"
        assert metric["layer"] in layers and metric["unit"] == "%"
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    lists = {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer") for m in spec[g]
             if "workloads" in m}
    listed = sorted(name for name, cells in lists.items() if CELL in cells)
    assert listed == sorted(NEW_READERS + APPENDED)
    for name in listed:
        assert lists[name][-1] == CELL
    assert not set(LEFT) & set(listed) and set(LEFT) <= set(lists)
    # the traffic is the three older T192 cells', key for key
    for other in ("nemotron_twotower_train_t192", "ouro_train_t192", BEFORE):
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
