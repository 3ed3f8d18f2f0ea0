"""The routed path end to end on the CPU through the same ``run.py`` the
chip runs: a tiny ``HybridNet`` cell (``tiny_hybrid/``) on the
``train_step_routed`` runner (``train_step`` under a second name: see that
file), its choices handed to the reference, the step's counters in
``run.counters``, all three comparisons held.

It has a directory and a file of its own because ``test_rehearsal.py`` holds
every number of ``compared`` under its limit, and ``choices_agreement`` is
printed as [agreement, floor], where higher is better.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid_rehearsal.py -q
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

TINY = os.path.join(HERE, "tiny_hybrid")
CELLS = rehearsal._load(os.path.join(TINY, "workloads"))
CONFIGS = rehearsal._load(os.path.join(TINY, "configs"))
SCOPE_READERS = ("ssd_roofline", "experts_roofline", "route_step_share", "ssd_step_share")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of the tiny routed cell: the real runners, readers,
    references and flops functions, and a BENCHMARK.json that hands every
    metric of the cells of its runner to it."""
    path = tmp_path_factory.mktemp("hybrid_root")
    for part in ("runners", "layer_metrics", "reference", "flops", "configs"):
        shutil.copytree(os.path.join(BENCH, part), path / part)
    for name, config in CONFIGS.items():
        shutil.copy(os.path.join(TINY, "configs", name + ".json"), path / "configs")
        shutil.copy(os.path.join(BENCH, "reference", config["reference"] + ".py"),
                    path / "reference" / (name + ".py"))
    shutil.copytree(os.path.join(TINY, "workloads"), path / "workloads")
    (path / "BENCHMARK.json").write_text(json.dumps(rehearsal._spec(tiny=CELLS)))
    return str(path)


def _run(root, workload, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
           "--workload", workload, "--seed", "2971215073",
           "--seconds", str(CELLS[workload]["rehearse_seconds"]), "--trace", str(trace),
           "--rehearse"]
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_routed_cell_rehearses_on_cpu(root, trace):
    proc = _run(root, "tiny_hybrid_train", trace)
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
    # what the step counted reached the run
    counters = earlier["counters"]
    assert counters["counter_rows_held"] > 0
    assert counters["counter_expert_rows_max"] >= counters["counter_expert_rows_mean"] > 0
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(CELLS["tiny_hybrid_train"]["answers"]["traced" if trace else "untraced"])
    # no device plane, no scope: the scope readers leave their metrics out
    assert not answered & set(SCOPE_READERS)


def test_every_new_metric_lists_the_cell_and_has_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    lists = {m["name"]: m["workloads"] for m in spec["per_layer"]}
    for name in SCOPE_READERS + ("expert_rows_max_over_mean",):
        assert lists[name] == ["nemotron_twotower_train_t192"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in ("setup_compile_s", "train_step_device_ms", "train_mfu", "train_roofline_share",
                 "device_idle_share"):
        assert lists[name][-1] == "nemotron_twotower_train_t192"
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))["nemotron_twotower_train_t192"]
    entry = next(w for w in spec["workloads"] if w["name"] == cell["name"])
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` is in the file under its own
    key, but for the cuts ``reduced`` lists (the depth, and with it the
    pattern string; the experts held; the vocabulary), in the file and in
    BENCHMARK.json alike; and ``net_args`` runs them."""
    config = rehearsal._load(os.path.join(BENCH, "configs"))["nemotron_twotower_30b_a3b"]
    assert config["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                 "n_routed_experts", "vocab_size"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == config["reduced"] and entry["source"] == config["source"]
    assert config["hybrid_override_pattern"] == config["env_args"]["net_args"]["pattern"]
    net = config["env_args"]["net_args"]
    assert (config["num_hidden_layers"], config["n_routed_experts"]) == (
        len(net["pattern"]), net["experts_held"]) == (9, 8)
    assert config["published"]["hybrid_override_pattern"].startswith(net["pattern"])
    same = {
        "hidden_size": "d_model", "mamba_num_heads": "mamba_heads",
        "mamba_head_dim": "mamba_head_dim", "n_groups": "n_groups",
        "ssm_state_size": "state_size", "conv_kernel": "conv_kernel", "chunk_size": "chunk",
        "num_experts_per_tok": "top_k", "moe_intermediate_size": "expert_width",
        "moe_shared_expert_intermediate_size": "shared_width",
        "routed_scaling_factor": "routed_scale", "num_attention_heads": "n_heads",
        "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim", "norm_eps": "norm_eps",
        "time_step_min": "dt_min", "time_step_max": "dt_max", "time_step_floor": "dt_floor",
    }
    for published, run_as in same.items():
        assert config[published] == net[run_as], published
    assert net["n_experts"] == config["published"]["n_routed_experts"] == 128
    for key in ("source", "assumed", "departures", "deployment", "reference_tolerance_why",
                "choices_agreement_floor_why", "reference_tolerance_f32_why"):
        assert config[key], key
