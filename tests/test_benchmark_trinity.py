"""The ``trinity_mini`` configuration and its cell ``trinity_mini_train_t192``
through the benchmark's own entry point on the CPU: the tiny cell of
``benchmark/tests/tiny_trinity/`` rehearsed by ``run.py --rehearse`` (a
workload of its own, ``benchmark_out/tiny_trinity_train``: it shares no output
directory with the other files that run ``run.py``), the two new readers on
the change and on a program without their scopes, the configuration's keys,
``flops/afmoe.py``'s count, and where PR 58's entries stand in
``BENCHMARK.json``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness
from benchmark.tests import test_rehearsal as rehearsal
from test_benchmark_kanana import _Fake, _read
from test_setup_readers import PR58, SETUP_READERS, before_pr58

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
TINY = os.path.join(BENCH, "tests", "tiny_trinity")
CELLS = rehearsal._load(os.path.join(TINY, "workloads"))
CONFIGS = rehearsal._load(os.path.join(TINY, "configs"))
CONFIG, CELL, NEW_READERS = PR58
# the accepted metrics the cell joined, each list's last name
APPENDED = ("trained_steps_per_s", "setup_compile_s", "train_step_device_ms", "train_mfu",
            "train_roofline_share", "device_idle_share", "attn_step_share", "route_step_share",
            "experts_roofline", "expert_rows_max_over_mean", "mlp_roofline",
            "norm_step_share") + SETUP_READERS
# the routed cells' metrics it stays out of: their entries are compared whole
# by the benchmark's own tests (test_expert_buffer_fill.py, test_phase_readers.py)
LEFT = ("expert_buffer_fill", "update_step_share", "packed_padding_share")
SCOPES = ["attn", "attn_proj", "qk_norm", "rope", "gqa", "attn_gate", "mlp", "route", "experts",
          "shared_expert", "norm"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of the tiny cell: the real runners, readers,
    references and flops functions, and a BENCHMARK.json that hands every
    metric of the cells of its runner to it."""
    path = tmp_path_factory.mktemp("trinity_root")
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
           "--workload", "tiny_trinity_train", "--seed", "2971215073",
           "--seconds", str(CELLS["tiny_trinity_train"]["rehearse_seconds"]), "--trace", str(trace),
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
    # what the step counted reached the run: four routed layers of top-3, four local
    # layers whose window of 6 cuts pairs, five gates that start half open
    counters = earlier["counters"]
    assert counters["counter_rows_held"] > 0 and counters["counter_expert_passes"] == 0
    assert 0 < counters["counter_window_pairs_cut"] < counters["counter_causal_pairs"]
    assert 0.3 < counters["counter_attn_gate_mean"] < 0.7
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(
        CELLS["tiny_trinity_train"]["answers"]["traced" if trace else "untraced"])
    # no device plane, no scope: the scope readers leave their metrics out
    assert not answered & {"attn_proj_roofline", "qk_gate_step_share", "experts_roofline",
                           "route_step_share", "attn_step_share", "mlp_roofline",
                           "norm_step_share"}
    assert earlier["counters"]["updates"] > 0


# -- the readers, on a made-up run ---------------------------------------------


class _Scoped(_Fake):
    def scope_work(self):
        return {"attn_proj": {"flops": 197e12 * 5e-3, "bytes": 819e9 * 1e-3}}


TRACED = {"attn_proj": {"seconds": 0.5, "ops": 90}, "qk_norm": {"seconds": 0.06, "ops": 40},
          "attn_gate": {"seconds": 0.04, "ops": 20}}


def test_the_new_readers_answer_on_the_change_by_hand():
    """The projections' operations bound them at 5 ms an update, 20 updates:
    100 ms of the 500 under ``attn_proj``: 20%; 0.06 + 0.04 s under the norms
    and the gate of the program's 2 s: 5%, 3 and 2 ms a step beside the
    projections' 25; a gate alone still answers."""
    run = _Scoped(TRACED, {})
    assert _read("attn_proj_roofline", run) == pytest.approx(20.0)
    assert _read("qk_gate_step_share", run) == pytest.approx(5.0)
    assert run.notes["qk_gate_ms_per_step"] == {
        "qk_norm": pytest.approx(3.0), "attn_gate": pytest.approx(2.0),
        "attn_proj": pytest.approx(25.0)}
    assert _read("qk_gate_step_share", _Scoped({"attn_gate": TRACED["attn_gate"]}, {})) == (
        pytest.approx(2.0))


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_answers_none_without_its_scope(name, monkeypatch):
    """The parent's program: no such scope in its trace and no constant
    beside one in ``models/hybrid.py``: the reader leaves its metric out and
    does not raise."""
    from handyrl_tpu.models import hybrid

    assert _read(name, _Scoped({}, {})) is None
    for constant in ("ATTN_PROJ_SCOPE", "QK_NORM_SCOPE", "ATTN_GATE_SCOPE"):
        monkeypatch.delattr(hybrid, constant)
    assert _read(name, _Scoped(TRACED, {})) is None


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
        return next(row for row in map(json.loads, f) if row["name"] == "Trinity-Mini")


def test_the_configuration_keeps_every_published_width():
    """Every key of the catalog's ``config`` is in the file under its own
    name, unchanged but for the cuts ``reduced`` lists (the depth, the experts
    held, the vocabulary), in the file and in BENCHMARK.json alike;
    ``net_args`` runs them; and the file says which published layers it runs,
    what it assumed, what it left out and what deployment it stands for."""
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] + " model_type afmoe"
    assert entry["file"] == "benchmark/configs/trinity_mini.json"
    assert config["published"] == {"num_hidden_layers": 32, "num_experts": 128,
                                   "vocab_size": 200192}
    net = config["env_args"]["net_args"]
    # published layers 0 and 3 to 6: local, then global, local, local, local
    run = config["layers_run"]
    assert run == [0, 3, 4, 5, 6] and config["num_hidden_layers"] == len(run) == 5
    kinds = {"sliding_attention": "W", "full_attention": "*"}
    assert net["pattern"][0::2] == "".join(kinds[config["layer_types"][i]] for i in run) == "W*WWW"
    assert net["pattern"][1::2] == "".join(
        "-" if i < config["num_dense_layers"] else "E" for i in run) == "-EEEE"
    assert config["num_experts"] == net["experts_held"] == 16
    assert (net["d_model"], net["n_heads"], net["n_kv_heads"], net["head_dim"]) == (
        config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"]) == (2048, 32, 4, 128)
    assert (net["window"], net["rope_theta"], net["norm_eps"]) == (
        config["sliding_window"], config["rope_theta"], config["rms_norm_eps"]) == (
        2048, 10000, 1e-5)
    assert net["rope_local_only"] and net["qk_norm"] and net["attn_gate"] and net["sandwich"]
    assert config["mup_enabled"] and net["embed_scale"] == pytest.approx(2048 ** 0.5)
    assert (net["mlp_width"], net["expert_width"], net["shared_width"]) == (
        config["intermediate_size"], config["moe_intermediate_size"],
        config["num_shared_experts"] * config["moe_intermediate_size"]) == (6144, 1024, 1024)
    assert (net["n_experts"], net["top_k"], net["routed_scale"]) == (
        config["published"]["num_experts"], config["num_experts_per_tok"],
        config["route_scale"]) == (128, 8, 2.826)
    assert net["router"] == config["score_func"] == "sigmoid" and config["route_norm"]
    assert net["gated_experts"] and config["hidden_act"] == "silu"
    assert config["n_group"] == config["topk_group"] == 1
    assert config["module"] == "HybridNet" and config["flops"] == "afmoe"
    assert len(config["assumed"]) >= 5 and "8 chips" in config["deployment"]
    assert any("modeling_afmoe.py" in line for line in config["assumed"])
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
    """``flops/afmoe.py``: twice the observed share, twice the operations; no
    term for padding, replay or an empty buffer slot; an expert term that
    follows the experts held; a local and a global layer cost the same while
    the window is the longer, and the window cuts keys once it is the shorter;
    by hand at the published widths a token's forward is 27.26M multiply-adds
    of projections an attention layer, 37.75M the dense layer, 9.70M an expert
    layer."""
    flops = harness.load_module(os.path.join(BENCH, "flops", "afmoe.py"))
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    work = flops.train_update(config, cell)
    assert work["parameters"] == 608_169_944
    assert work["tokens"] == pytest.approx(64 * (184 * 0.413 + 8 * 0.127))
    net = config["env_args"]["net_args"]
    proj = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert flops.attn_proj_macs_per_token(net) == proj == 27_262_976
    in_a_row = 184 * 0.413 + 8 * 0.127
    for kind in "W*":
        assert flops.layer_macs_per_token(net, kind, in_a_row) == pytest.approx(
            proj + (in_a_row + 1) / 2 * 32 * 256)
    assert flops.layer_macs_per_token(dict(net, window=16), "W", in_a_row) == proj + 16 * 32 * 256
    assert flops.layer_macs_per_token(net, "-", 0) == 3 * 2048 * 6144
    assert flops.layer_macs_per_token(net, "E", 0) == pytest.approx(
        2048 * 128 + 3 * 2048 * 1024 + 1.0 * 3 * 2048 * 1024)
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
    assert set(scoped) == {"experts", "attn_proj", "mlp"}
    # the one dense sub-layer: 37.75M multiply-adds a token, forward once and backward twice
    assert scoped["mlp"]["flops"] == pytest.approx(
        2 * 37_748_736 * 64 * (3 * 184 * 0.413 + 8 * 0.127))
    assert scoped["mlp"]["flops"] * 5 * proj == pytest.approx(
        scoped["attn_proj"]["flops"] * 37_748_736)
    assert scoped["experts"]["rows"] == pytest.approx(4 * 1.0 * work["tokens"])
    # the five gated attention sub-layers' projections are the update's work
    assert 0.55 * work["flops"] < scoped["attn_proj"]["flops"] < 0.62 * work["flops"]


def test_the_entries_are_appended_and_nothing_else_moved():
    """PR 58's: one configuration, one cell and two metrics at the end of
    their lists, the cell's name at the end of the lists of the accepted
    metrics whose readers answer for it, and in no other: not in the three
    whose entries the benchmark's own tests compare whole; with them taken off
    (``before_pr58``) the file is what the PRs before held it to."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [c["name"] for c in spec["configs"][-2:]] == ["kanana_2_30b_a3b", CONFIG]
    assert [w["name"] for w in spec["workloads"][-2:]] == ["kanana2_train_t192", CELL]
    assert len(spec["workloads"]) == 9 and sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    entry = spec["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])
    assert len(entry["why"]) <= 200 and len(spec["configs"][-1]["why"]) <= 200
    assert len(spec["configs"][-1]["source"]) <= 200
    assert cell["runner"] == "train_step_routed" and entry["chips"] == 1
    assert cell["scopes"] == SCOPES == CELLS["tiny_trinity_train"]["scopes"]
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-2:] == list(NEW_READERS)
    layers = {m["layer"] for m in spec["per_layer"][:-2]}
    for metric in spec["per_layer"][-2:]:
        assert metric["workloads"] == [CELL] and metric["moves"] == "trained_steps_per_s"
        assert metric["layer"] in layers and metric["unit"] == "%"
        assert metric["source"] == "device_trace"
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    lists = {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer") for m in spec[g]
             if "workloads" in m}
    listed = sorted(name for name, cells in lists.items() if CELL in cells)
    assert listed == sorted(NEW_READERS + APPENDED)
    for name in listed:
        assert lists[name][-1] == CELL
    assert not set(LEFT) & set(listed) and set(LEFT) <= set(lists)
    # the traffic is the four older T192 cells', key for key
    for other in ("nemotron_twotower_train_t192", "ouro_train_t192", "zaya1_train_t192",
                  "kanana2_train_t192"):
        theirs = rehearsal._load(os.path.join(BENCH, "workloads"))[other]
        for key in ("traffic", "runner", "chips", "train_args", "mesh", "lr", "n_batches",
                    "fill_episodes", "in_flight", "programs"):
            assert cell[key] == theirs[key], (other, key)
    # a cell lists a per-layer metric only where it reports what that metric moves
    reports = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"trained_steps_per_s", "setup_s"}
    for metric in spec["per_layer"]:
        if CELL in metric["workloads"]:
            assert metric["moves"] in reports | {"setup_s"}, metric["name"]
    before = before_pr58(spec)
    assert CELL not in json.dumps(before) and CONFIG not in [c["name"] for c in before["configs"]]
