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

TINY = os.path.join(HERE, "tiny")


def _load(folder):
    """name -> the JSON object of each file of ``folder``."""
    found = {}
    for name in sorted(os.listdir(folder)):
        if name.endswith(".json"):
            with open(os.path.join(folder, name)) as f:
                found[name[:-5]] = json.load(f)
    return found


# The tiny cells and configurations are files (tiny/workloads, tiny/configs):
# a later PR adds its rehearsal, with a runner of its own if it needs one, as
# two files.  A tiny configuration names the ``reference`` file it borrows; a
# tiny cell its ``rehearse_seconds`` and the metrics its rehearsal ``answers``
# without a device trace.
CELLS = _load(os.path.join(TINY, "workloads"))
TINY_CONFIGS = _load(os.path.join(TINY, "configs"))


def _spec(workloads=os.path.join(BENCH, "workloads"), tiny=None):
    """The repo's BENCHMARK.json with every metric handed to the tiny cells
    of its cells' runners, so the rehearsal walks every reader.  A cell
    whose runner has no tiny cell hands its metrics to none."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_runner = {}
    for name, cell in (CELLS if tiny is None else tiny).items():
        by_runner.setdefault(cell["runner"], []).append(name)
    runner_of = {cell["name"]: cell["runner"] for cell in _load(workloads).values()}
    for group in ("end_to_end", "per_layer"):
        for metric in spec[group]:
            if "workloads" in metric:
                metric["workloads"] = sorted({
                    tiny_cell for cell in metric["workloads"]
                    for tiny_cell in by_runner.get(runner_of[cell], [])})
    return spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root of tiny cells: the real runners, readers,
    references, flops functions and configurations, the tiny cell and
    configuration files and a BENCHMARK.json that lists every metric for
    every tiny cell of its runner."""
    path = tmp_path_factory.mktemp("bench_root")
    for part in ("runners", "layer_metrics", "reference", "flops", "configs"):
        shutil.copytree(os.path.join(BENCH, part), path / part)
    for name, config in TINY_CONFIGS.items():
        shutil.copy(os.path.join(TINY, "configs", name + ".json"), path / "configs")
        shutil.copy(os.path.join(BENCH, "reference", config["reference"] + ".py"),
                    path / "reference" / (name + ".py"))
    shutil.copytree(os.path.join(TINY, "workloads"), path / "workloads")
    (path / "BENCHMARK.json").write_text(json.dumps(_spec()))
    return str(path)


def _run(root, workload, trace, rehearse=True, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--root", root,
           "--workload", workload, "--seed", "3",
           "--seconds", str(CELLS[workload]["rehearse_seconds"]), "--trace", str(trace)]
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
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    # each number compared beside its limit, and again as stderr's last lines
    assert last["compared"]["failed"] == [0, 0]
    assert all(number <= limit for number, limit in last["compared"].values())
    assert "policy" in last["compared"]
    for name in last["compared"]:
        assert f"benchmark: compared {name} " in proc.stderr[-2000:]
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
    expected = CELLS[workload]["answers"]["traced" if trace else "untraced"]
    assert answered >= set(expected), answered


def test_cpu_run_without_rehearse_prints_no_result(root):
    proc = _run(root, "tiny_train", 0, rehearse=False)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


def test_a_cell_of_an_unknown_runner_hands_its_metrics_to_no_tiny_cell(tmp_path):
    """A later PR's cell with a runner of its own, before its tiny cell is
    written: the metrics it reports go on being handed to the others'."""
    shutil.copytree(os.path.join(BENCH, "workloads"), tmp_path / "workloads")
    for name in ("xfmr_train_t64", "xfmr_train_t64_dp4"):
        cell = dict(CELLS["tiny_train"], name=name, runner="routed_step")
        (tmp_path / "workloads" / (name + ".json")).write_text(json.dumps(cell))
    spec = _spec(workloads=str(tmp_path / "workloads"))
    lists = {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer")
             for m in spec[g] if "workloads" in m}
    assert lists["train_step_device_ms"] == []
    assert lists["trained_steps_per_s"] == lists["epoch_stall_share"] == ["tiny_loop"]
    # with a tiny cell of that runner, it is handed them
    tiny = dict(CELLS, tiny_routed=dict(cell, name="tiny_routed"))
    spec = _spec(workloads=str(tmp_path / "workloads"), tiny=tiny)
    assert "tiny_routed" in next(
        m["workloads"] for m in spec["per_layer"] if m["name"] == "train_step_device_ms")


def test_every_cell_lists_device_idle_share_and_setup_compile_s():
    """A new training cell reports the end-to-end metrics these two move;
    a metric without a list would have to be answered by every such cell."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = sorted(cell["name"] for cell in spec["workloads"])
    for metric in spec["per_layer"]:
        assert "workloads" in metric, metric["name"]
        if metric["name"] in ("device_idle_share", "setup_compile_s"):
            assert sorted(metric["workloads"]) == cells


@pytest.mark.parametrize("name", sorted(_load(os.path.join(BENCH, "configs"))) + sorted(TINY_CONFIGS))
def test_every_configuration_names_its_module(name):
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env

    config = {**_load(os.path.join(BENCH, "configs")), **TINY_CONFIGS}[name]
    cfg = normalize_args({"env_args": dict(config["env_args"]), "train_args": {}})
    assert type(make_env(cfg["env_args"]).net()).__name__ == config["module"]


@pytest.mark.parametrize("workload", ["tiny_train", "tiny_loop"])
def test_a_net_the_program_lacks_is_refused_at_once(root, tmp_path, workload):
    """A configuration whose ``module`` this program does not build (a later
    PR's, run by its parent commit): exit 3 within seconds, before a batch
    or a program is made, and no line on stdout."""
    import time

    stub = tmp_path / "root"
    shutil.copytree(root, stub)
    for path in (stub / "configs").iterdir():
        config = json.loads(path.read_text())
        path.write_text(json.dumps(dict(config, module="NoSuchNet")))
    t0 = time.monotonic()
    proc = _run(str(stub), workload, 0)
    assert time.monotonic() - t0 < 20.0
    assert proc.returncode == 3, proc.stderr[-4000:]
    assert proc.stdout.strip() == ""
    assert "is run with the module NoSuchNet" in proc.stderr.strip().splitlines()[-1]


def test_counters_of_the_step_reach_the_run(root, monkeypatch, capsys):
    """``counter_*`` keys of the step's metrics are fetched with the losses
    and their mean per update goes to ``run.counters``; the tiny train
    cell, in this process, with a step that counts."""
    import faulthandler

    sys.path.insert(0, REPO)
    from benchmark import run as entry
    from handyrl_tpu.parallel import TrainContext

    real, calls = TrainContext.train_step, []

    def counting(self, state, batch, lr):
        state, metrics = real(self, state, batch, lr)
        calls.append(1)
        return state, dict(metrics, counter_rows_routed=metrics["dcnt"] * 0 + len(calls) % 2,
                           count_not_a_counter=metrics["dcnt"])

    monkeypatch.setattr(TrainContext, "train_step", counting)
    try:
        code = entry.main(["--root", root, "--workload", "tiny_train", "--seed", "5",
                           "--seconds", "1", "--trace", "0", "--rehearse"])
    finally:
        faulthandler.cancel_dump_traceback_later()     # main armed it in this process
    assert code == entry.EXIT_REHEARSAL
    earlier = json.loads(capsys.readouterr().out.strip().splitlines()[-2])
    counters = earlier["counters"]
    # alternating 1, 0 over the window's updates (the two warm-ups came first)
    assert counters["counter_rows_routed"] == pytest.approx(0.5, abs=0.5 / counters["updates"])
    assert "count_not_a_counter" not in counters


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
