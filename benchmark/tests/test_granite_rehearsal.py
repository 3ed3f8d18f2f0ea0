"""The actor cell end to end on the CPU through the same ``run.py`` the chip
runs: a tiny ``HybridNet`` of the ``granitemoehybrid`` family
(``tiny_granite/``) on the ``actor_stream`` runner: ``actor_loop`` against a
loopback ``PlaneGateway``, the window on the gateway's clock, the replay of
two games at the timed rows held to what the window recorded and to the
plain reference on all three limits, the step mode's counters in
``run.counters``, every new reader walked.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_granite_rehearsal.py -q
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

TINY = os.path.join(HERE, "tiny_granite")
CELLS = rehearsal._load(os.path.join(TINY, "workloads"))
CONFIGS = rehearsal._load(os.path.join(TINY, "configs"))
CELL, CONFIG = "granite_actor_b32", "granite_4_0_h_small"
NEW_READERS = ("rollout_mfu", "rollout_roofline_share", "rollout_experts_share",
               "rollout_state_share", "act_expert_buffer_fill")
# accepted metrics whose readers answer for the cell from what it leaves
APPENDED = ("selfplay_steps_per_s", "setup_compile_s", "rollout_device_share",
            "rollout_ms_per_dispatch", "rollout_env_share")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of the tiny actor cell: the real runners, readers,
    references and flops functions, and a BENCHMARK.json that hands every
    metric of the cells of its runner to it."""
    path = tmp_path_factory.mktemp("granite_root")
    for part in ("runners", "layer_metrics", "reference", "flops", "configs"):
        shutil.copytree(os.path.join(BENCH, part), path / part)
    for name, config in CONFIGS.items():
        shutil.copy(os.path.join(TINY, "configs", name + ".json"), path / "configs")
        shutil.copy(os.path.join(BENCH, "reference", config["reference"] + ".py"),
                    path / "reference" / (name + ".py"))
    shutil.copytree(os.path.join(TINY, "workloads"), path / "workloads")
    (path / "BENCHMARK.json").write_text(json.dumps(rehearsal._spec(tiny=CELLS)))
    return str(path)


def _run(root, workload, trace, seed="2971215073"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
           "--workload", workload, "--seed", seed,
           "--seconds", str(CELLS[workload]["rehearse_seconds"]), "--trace", str(trace),
           "--rehearse"]
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_actor_cell_rehearses_on_cpu(root, trace):
    proc = _run(root, "tiny_granite_actor", trace)
    assert proc.returncode == 4, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last, earlier = json.loads(lines[-1]), json.loads(lines[-2])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    # the replay against the window, then the three limits of a routed net
    compared = last["compared"]
    assert {"replay_prob", "replay_value", "policy", "value", "return", "choices_agreement",
            "f32_policy", "f32_value", "f32_return"} <= set(compared)
    for name in ("choices_agreement", "f32_choices_agreement"):     # higher is better
        agreement, floor = compared.pop(name)
        assert agreement >= floor
    assert all(number <= limit for number, limit in compared.values()), compared
    checks = earlier["checks"]
    assert checks.pop("device_is_tpu") is False
    checks.pop("device_ran", None)       # a CPU trace has no device plane
    assert all(checks.values()), (checks, earlier["notes"])
    assert checks["replay_matches_window"] and checks["matches_reference"] \
        and checks["choices_agree"] and checks["matches_reference_f32"] \
        and checks["no_compile_in_window"] and checks["actor_loop_ended"]
    # whole dispatches of lanes x k, through the gateway
    counters, cell = earlier["counters"], CELLS["tiny_granite_actor"]["train_args"]
    per_dispatch = cell["device_rollout_games"] * cell["device_replay_k_steps"]
    assert counters["game_steps"] == counters["dispatches"] * per_dispatch > 0
    assert counters["dispatches_before_window"] >= 3
    assert earlier["notes"]["param_dtypes"] == ["bfloat16"]
    assert earlier["notes"]["judged_games"]["observed_steps"] > 4
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(CELLS["tiny_granite_actor"]["answers"]["traced" if trace else "untraced"])
    if trace:
        # what the step mode counted reached the run: three routed layers, every
        # row the rollout applies the net to, top-3 of 8 with 4 held
        rows = cell["device_rollout_games"] * 2 * cell["device_replay_k_steps"]
        assert 0 < counters["counter_rows_held"] <= 3 * 3 * rows
        assert counters["counter_buffer_slots"] >= 3 * cell["device_replay_k_steps"] * 128
        # no device plane, no program, no scope: those readers leave their metrics out
        assert not answered & {"rollout_roofline_share", "rollout_experts_share",
                               "rollout_state_share", "rollout_device_share",
                               "rollout_ms_per_dispatch", "rollout_env_share"}


def test_a_control_of_eight_bit_weights_fails_the_rehearsal(root, tmp_path):
    """The same cell with ``control``: the replay runs on weights rounded
    through float8, and is neither what the window recorded nor what the
    reference gives on the weights the window acted on."""
    shutil.copytree(root, tmp_path / "root")
    path = tmp_path / "root" / "workloads" / "tiny_granite_actor.json"
    path.write_text(json.dumps(dict(CELLS["tiny_granite_actor"], control="float8_e4m3fn")))
    proc = _run(str(tmp_path / "root"), "tiny_granite_actor", 0)
    assert proc.returncode == 4, proc.stderr[-4000:]
    earlier = json.loads(proc.stdout.strip().splitlines()[-2])
    assert earlier["notes"]["control"] == "float8_e4m3fn"
    assert not earlier["checks"]["replay_matches_window"] and not earlier["checks"]["matches_reference"]
    # the float32 comparison reads the weights the window acted on, on both sides: it holds
    assert earlier["checks"]["matches_reference_f32"]


def test_the_entries_are_appended_and_nothing_else_moved():
    """One configuration, one cell and five metrics at the end of their
    lists, the cell's name at the end of the lists of the accepted metrics
    whose readers answer for it, and in no other."""
    spec = _benchmark()
    assert spec["configs"][-1]["name"] == CONFIG and spec["workloads"][-1]["name"] == CELL
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    entry = spec["workloads"][-1]
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])
    assert len(entry["why"]) <= 200 and len(spec["configs"][-1]["why"]) <= 200
    assert cell["runner"] == "actor_stream" and entry["chips"] == 1
    assert [m["name"] for m in spec["per_layer"][-5:]] == list(NEW_READERS)
    layers = {m["layer"] for m in spec["per_layer"][:-5]}
    for metric in spec["per_layer"][-5:]:
        assert metric["workloads"] == [CELL] and metric["moves"] == "selfplay_steps_per_s"
        assert metric["layer"] in layers and metric["unit"] == "%"
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    lists = {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer") for m in spec[g]
             if "workloads" in m}
    listed = sorted(name for name, cells in lists.items() if CELL in cells)
    assert listed == sorted(NEW_READERS + APPENDED)
    for name in listed:
        assert lists[name][-1] == CELL
    # a cell lists a per-layer metric only where it reports what that metric moves
    reports = {m["name"] for m in spec["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert reports == {"selfplay_steps_per_s", "setup_s"}
    for metric in spec["per_layer"]:
        if CELL in metric["workloads"]:
            assert metric["moves"] in reports, metric["name"]


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` is in the file under its own
    key, but for the cuts ``reduced`` lists (the depth and with it the layer
    types, the experts held, the vocabulary), in the file and in
    BENCHMARK.json alike; and ``net_args`` runs them."""
    config = rehearsal._load(os.path.join(BENCH, "configs"))[CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "layer_types", "num_local_experts",
                                 "vocab_size"]
    entry = next(c for c in _benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-small")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in config["reduced"]:
                assert config["published"][key] == value, key
            else:
                assert key in config and config[key] == value, key
    net = config["env_args"]["net_args"]
    # one period: the published pattern's first ten layers, a mixer and an expert sub-layer each
    period = config["published"]["layer_types"][:10]
    assert config["layer_types"] == period and config["num_hidden_layers"] == 10
    assert net["pattern"] == "".join({"mamba": "ME", "attention": "*E"}[kind] for kind in period)
    assert config["published"]["layer_types"] == period * 4
    assert (config["num_local_experts"], net["n_experts"]) == (
        net["experts_held"], config["published"]["num_local_experts"]) == (36, 72)
    same = {
        "hidden_size": "d_model", "mamba_n_heads": "mamba_heads", "mamba_d_head": "mamba_head_dim",
        "mamba_n_groups": "n_groups", "mamba_d_state": "state_size", "mamba_d_conv": "conv_kernel",
        "mamba_chunk_size": "chunk", "num_experts_per_tok": "top_k",
        "intermediate_size": "expert_width", "shared_intermediate_size": "shared_width",
        "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "rms_norm_eps": "norm_eps", "attention_multiplier": "attn_score_scale",
        "residual_multiplier": "residual_scale", "embedding_multiplier": "embed_scale",
        "logits_scaling": "logits_divisor",
    }
    for published, run_as in same.items():
        assert config[published] == net[run_as], published
    assert net["mamba_heads"] * net["mamba_head_dim"] == config["mamba_expand"] * net["d_model"]
    assert net["head_dim"] * net["n_heads"] == net["d_model"]
    assert (net["router"], net["gated_experts"], net["param_dtype"]) == ("softmax", True, "bfloat16")
    assert "rope_theta" not in net and config["position_embedding_type"] == "nope"
    for key in ("source", "assumed", "departures", "deployment", "dtype", "reference_tolerance_why",
                "choices_agreement_floor_why", "reference_tolerance_f32_why",
                "choices_agreement_floor_f32_why", "replay_tolerance_why"):
        assert config[key], key


def test_the_cell_is_the_traffic_the_issue_fixed():
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    assert cell["train_args"] == {"observation": False, "device_rollout_games": 32,
                                  "device_replay_k_steps": 16}
    assert (cell["traffic"], cell["warm_dispatches"], cell["judge_lanes"]) == (
        "actor_stream_b32_k16", 2, [0, 1])
    assert "control" not in cell


def test_the_step_the_flops_file_counts_is_the_arithmetic_of_the_issue():
    """4.55B parameters held in bfloat16 are 9.1 GB; a (lane, player) row's
    state is 40.3 MB in float32; a step carries 32 tokens."""
    configs = rehearsal._load(os.path.join(BENCH, "configs"))
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[CELL]
    flops = harness.load_module(os.path.join(BENCH, "flops", "granite_moe_hybrid.py"))
    net = configs[CONFIG]["env_args"]["net_args"]
    assert flops.sublayer_parameters(net, "M") == 102_291_072
    assert flops.sublayer_parameters(net, "*") == 41_943_040 + 4096
    assert flops.sublayer_parameters(net, "E") == 4096 + 4096 * 72 + 3 * 4096 * (1536 + 36 * 768)
    work = flops.act_step(configs[CONFIG], cell)
    # what ``jax.eval_shape`` of the module's own ``init`` counts (tests/test_granite_net.py)
    assert work["tokens"] == 32 and work["parameters"] == 4_570_467_160
    assert work["weight_bytes"] == 2 * work["parameters"]
    assert flops.state_bytes_per_row(net) == 4 * (9 * (128 * 64 * 128 + 3 * 8448) + 2 * 200 * 8 * 128)
    assert work["state_bytes"] == 2 * 32 * flops.state_bytes_per_row(net)
    # bound by bytes: 14.3 ms of bandwidth against 0.54 ms of products at the peak
    assert work["bytes"] / 819e9 > 20 * work["flops"] / 197e12
    # the scopes' counts are parts of the whole
    inside = flops.scope_work(configs[CONFIG], cell)
    assert sum(v["bytes"] for k, v in inside.items() if k != "ssd") < work["weight_bytes"]
    assert inside["experts"]["rows"] == 10 * 10 * 36 / 72 * 32


@pytest.mark.parametrize("name", NEW_READERS + ("actor_step",))
def test_a_new_reader_answers_none_on_a_run_without_its_scope_or_counter(name):
    """On a standing cell (no acting step in its flops file, no rollout
    program in its trace, none of the actor loop's counters): ``None``, and
    nothing raised; so on the parent, whose traced runs load these files."""
    run = harness.Run(BENCH, "nemotron_twotower_train_t192", seed=1, seconds=30, trace=True,
                      rehearse=True, t_process=0.0)
    run.counters.update(updates=10, window_s=3.0, counter_rows_held=5.0, counter_buffer_slots=9.0)
    reader = harness.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert reader.read(run) is None
    run.reduced = {"window_s": 3.0, "busy_s": 2.9, "idle_s": 0.1, "programs": {}, "scopes": {}}
    assert reader.read(run) is None
