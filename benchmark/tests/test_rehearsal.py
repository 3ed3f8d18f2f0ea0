"""Every runner end to end on the CPU, at a tiny size, through the same
``run.py`` the chip runs: warm-up, window, stop, counters, reference check,
the last line's keys.  Plus the whole d1536 dp=4 step compiled for a
described v5e 2x2.  Run by hand and before a chip call:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

A rehearsal must never look like a result: ``--rehearse`` prints
``correct: false``, no metric, and exits non-zero; without it a CPU run
prints nothing and exits non-zero.
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

TINY_XFMR = {
    "name": "tiny_xfmr", "source": "test",
    "env_args": {"env": "Geister", "net": "transformer",
                 "net_args": {"d_model": 32, "n_heads": 2, "n_layers": 2, "memory_len": 4}},
    "train_args": {},
    "shapes": {"observation_width": 270, "players": 2, "actions": 214, "scalar_heads": 2},
    "flops": "alibi_transformer", "reference_tolerance": 1e-3,
}
CELLS = {
    "tiny_loop": {
        "config": "geesenet", "runner": "device_loop", "chips": 1,
        "train_args": {
            "turn_based_training": False, "observation": False, "device_replay": True,
            "eval_rate": 0.0, "eval": {"opponent": ["rulebase"]},
            "worker": {"num_parallel": 1}, "batch_size": 16, "forward_steps": 8,
            "device_rollout_games": 16, "device_replay_k_steps": 16,
            "device_replay_slots": 128, "fused_steps": 2, "device_eval_games": 8,
            "update_episodes": 40, "minimum_episodes": 450,     # fills the 16 x 128 ring
        },
        "warm_records": 2, "trace_seconds": 2, "trace_updates": 8, "check_samples": 16,
        "stall_spans": ["epoch.snapshot_wait"],
    },
    "tiny_train": {
        "config": "tiny_xfmr", "runner": "train_step", "chips": 1,
        "train_args": {"batch_size": 4, "burn_in_steps": 2, "forward_steps": 6,
                       "observation": True, "seq_attention": "einsum"},
        "mesh": {"dp": 1}, "lr": 1e-5, "n_batches": 2, "fill_episodes": 4,
        "in_flight": 2, "trace_seconds": 1, "programs": {"train": "jit__step"},
    },
    "tiny_train_dp4": {
        "config": "tiny_xfmr", "runner": "train_step", "chips": 4,
        "train_args": {"batch_size": 8, "burn_in_steps": 2, "forward_steps": 6,
                       "observation": True, "seq_attention": "einsum"},
        "mesh": {"dp": 4}, "lr": 1e-5, "n_batches": 2, "fill_episodes": 4,
        "in_flight": 2, "trace_seconds": 1, "programs": {"train": "jit__step"},
    },
}
SECONDS = {"tiny_loop": 8, "tiny_train": 2, "tiny_train_dp4": 2}


def _spec():
    """The repo's BENCHMARK.json with every metric handed to the tiny cell
    of its runner, so the rehearsal walks every reader."""
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    by_runner = {"device_loop": ["tiny_loop"], "train_step": ["tiny_train", "tiny_train_dp4"]}
    runner_of = {}
    for cell in os.listdir(os.path.join(BENCH, "workloads")):
        data = json.load(open(os.path.join(BENCH, "workloads", cell)))
        runner_of[data["name"]] = data["runner"]
    for group in ("end_to_end", "per_layer"):
        for metric in spec[group]:
            if "workloads" in metric:
                metric["workloads"] = sorted({
                    tiny for cell in metric["workloads"] for tiny in by_runner[runner_of[cell]]})
    return spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of tiny cells: the real runners, readers,
    references and flops functions, the test's own cell and config files
    and a BENCHMARK.json that lists every metric for every cell."""
    path = tmp_path_factory.mktemp("bench_root")
    for part in ("runners", "layer_metrics", "reference", "flops"):
        shutil.copytree(os.path.join(BENCH, part), path / part)
    shutil.copy(os.path.join(BENCH, "reference", "xfmr_d1536.py"),
                path / "reference" / "tiny_xfmr.py")
    os.makedirs(path / "configs")
    os.makedirs(path / "workloads")
    shutil.copy(os.path.join(BENCH, "configs", "geesenet.json"), path / "configs")
    (path / "configs" / "tiny_xfmr.json").write_text(json.dumps(TINY_XFMR))
    for name, cell in CELLS.items():
        (path / "workloads" / (name + ".json")).write_text(json.dumps(dict(cell, name=name)))
    (path / "BENCHMARK.json").write_text(json.dumps(_spec()))
    return str(path)


def _run(root, workload, trace, rehearse=True, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
           "--workload", workload, "--seed", "3",
           "--seconds", str(SECONDS[workload]), "--trace", str(trace)]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", sorted(CELLS))
def test_runner_rehearses_on_cpu(root, workload, trace):
    proc = _run(root, workload, trace, devices=CELLS[workload]["chips"])
    assert proc.returncode == 4, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    last, earlier = json.loads(lines[-1]), json.loads(lines[-2])
    assert set(last) == {"correct", "attempted", "failed", "metrics", "device"}
    # never a rate, a share or a time under a device metric's name
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    checks = earlier["checks"]
    assert checks.pop("device_is_tpu") is False
    checks.pop("device_ran", None)       # a CPU trace has no device plane
    assert all(checks.values()), (checks, earlier["notes"])
    assert checks["matches_reference"] and checks["no_compile_in_window"]
    assert earlier["window_s"] > 0
    assert earlier["counters"]["compiles_in_window"] == 0
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= EXPECTED[CELLS[workload]["runner"]][trace], answered


# what a rehearsal's readers must answer without a device trace
EXPECTED = {
    "device_loop": [{"trained_steps_per_s", "selfplay_steps_per_s", "setup_s"},
                    {"setup_compile_s", "train_mfu"}],
    "train_step": [{"trained_steps_per_s", "setup_s"}, {"setup_compile_s", "train_mfu"}],
}


def test_cpu_run_without_rehearse_prints_no_result(root):
    proc = _run(root, "tiny_train", 0, rehearse=False)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


def test_a_directory_without_the_program_fails(tmp_path):
    """BENCHMARK.json and the benchmark's paths alone are not a result."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "xfmr_train_t64",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.mark.parametrize("cell_name", ["xfmr_train_t64_dp4", "xfmr_train_t64"])
def test_d1536_step_compiles_for_a_described_v5e(v5e_2x2, cell_name):
    """The whole train step of the xfmr cells at the published widths,
    compiled by the TPU compiler for chips that are described and not
    attached: fits 16 GB a chip, and the dp=4 program holds an all-reduce."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    sys.path.insert(0, REPO)
    from benchmark import traffic
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext

    cell = json.load(open(os.path.join(BENCH, "workloads", cell_name + ".json")))
    config = json.load(open(os.path.join(BENCH, "configs", cell["config"] + ".json")))
    cfg = normalize_args({
        "env_args": dict(config["env_args"]),
        "train_args": dict(config["train_args"], **cell["train_args"]),
    })
    args = dict(cfg["train_args"], env=cfg["env_args"])
    env = make_env(args["env"])
    module = env.net()
    dp = cell["mesh"]["dp"]
    mesh = Mesh(np.asarray(v5e_2x2.devices[:dp]).reshape(dp), ("dp",))
    ctx = TrainContext(module, args, mesh)

    tiny = dict(args, batch_size=dp)
    host_batch = traffic.random_play_batches(env, module, tiny, 1, 2)[0]
    grow = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        (args["batch_size"],) + x.shape[1:], x.dtype, sharding=ctx._batch_shard)
    batch = jax.tree.map(grow, host_batch)
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), obs, module.initial_state((1,))))["params"]
    state = jax.eval_shape(lambda p: {"params": p, "opt_state": ctx.tx.init(p),
                                      "steps": jnp.zeros((), jnp.int32)}, params)
    replicated = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
        x.shape, x.dtype, sharding=ctx._replicated)
    state = jax.tree.map(replicated, state)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = ctx._bind(state).lower(
            state, batch, jax.ShapeDtypeStruct((), jnp.float32, sharding=ctx._replicated)
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    memory = compiled.memory_analysis()
    held = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert held < 15.5e9, held
    text = compiled.as_text()
    assert ("all-reduce" in text) == (dp > 1)
    assert "tpu_custom_call" not in text      # einsum: no Pallas kernel on this path
    print(cell_name, "bytes a chip:", held)
