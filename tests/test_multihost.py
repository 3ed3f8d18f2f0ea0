"""Multi-host learner plane: jax.distributed over two localhost processes.

The reference has no multi-host learner at all (nn.DataParallel is
single-process, reference train.py:340-341); SURVEY.md §2.5 prescribes
jax.distributed + XLA collectives for the gradient plane.  This test runs
TWO real OS processes, each with 2 virtual CPU devices, connected through
``init_distributed`` — the global mesh spans 4 devices — and checks:

* a dp-sharded global array assembled from per-process local shards
  (``TrainContext.put_batch``'s multi-process path) reduces correctly
  through a jitted collective;
* only the coordinator (process 0) passes the checkpoint/metrics guard.
"""

import json
import os
import subprocess
import sys

import pytest
from conftest import free_port

# the whole multi-process surface runs in its own 2-process CI steps
# (fast leg: epoch loop + resume broadcast + init timeout; slow leg: the
# host-loss / coordinator-death e2es), excluded from the general legs
pytestmark = pytest.mark.multihost

_CHILD = r"""
import json, os, sys

port, pid, nproc, outdir = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from handyrl_tpu.parallel import (
    init_distributed,
    is_coordinator,
    local_batch_size,
    make_mesh,
)

rank = init_distributed(
    {"coordinator_address": f"127.0.0.1:{port}", "num_processes": nproc, "process_id": pid}
)
assert rank == pid, (rank, pid)
assert jax.process_count() == nproc
assert len(jax.devices()) == 2 * nproc  # global device view

mesh = make_mesh({"dp": -1})
sharding = NamedSharding(mesh, PartitionSpec("dp"))

# per-process local shard of a global batch: process p contributes rows p+1
B_local = local_batch_size(4)
local = np.full((B_local, 3), pid + 1.0, np.float32)
arr = jax.make_array_from_process_local_data(sharding, local)
total = jax.jit(lambda x: x.sum(), out_shardings=NamedSharding(mesh, PartitionSpec()))(arr)

# put_batches' multi-process branch (fused_steps path): stack k local
# batch shards -> (k, B, ...) global tree, reduce through a collective
from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.parallel import TrainContext

cfg = normalize_args({"env_args": {"env": "TicTacToe"}, "train_args": {"batch_size": 4}})
targs = dict(cfg["train_args"]); targs["env"] = cfg["env_args"]
ctx = TrainContext(make_env(cfg["env_args"]).net(), targs, mesh)
host_batches = [
    {"action": np.full((B_local, 1), pid + 1.0, np.float32)} for _ in range(3)
]
stacked = ctx.put_batches(host_batches)
ssum = jax.jit(
    lambda t: t["action"].sum(), out_shardings=NamedSharding(mesh, PartitionSpec())
)(stacked)
# 3 stacked batches x (2 local rows x 1 col) x (1 + 2) across processes
assert abs(float(ssum) - 18.0) < 1e-6, float(ssum)

# the checkpoint/metrics guard: exactly one writer
if is_coordinator():
    with open(os.path.join(outdir, "result.json"), "w") as f:
        json.dump({"total": float(total), "process_count": jax.process_count()}, f)
else:
    with open(os.path.join(outdir, f"noncoord_{pid}.txt"), "w") as f:
        f.write("guarded")
"""


# The gradient plane's core claim (SURVEY §2.5): TrainContext.train_step
# — value_and_grad + the GSPMD gradient all-reduce — executed ACROSS
# processes on per-process local batch shards must produce the same
# params on every process, and the same update a single process computes
# from the full batch.  Both processes seed identically, generate the
# SAME episodes/windows via the real generator, then feed only their own
# rows through put_batch's make_array_from_process_local_data branch.
_TRAIN_CHILD = r"""
import json, os, sys

port, pid, nproc, outdir = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
mesh_spec = json.loads(sys.argv[5]) if len(sys.argv) > 5 else {"dp": -1}
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax

import numpy as np

from handyrl_tpu.parallel import init_distributed, is_coordinator, make_mesh

init_distributed(
    {"coordinator_address": f"127.0.0.1:{port}", "num_processes": nproc, "process_id": pid}
)

sys.path.insert(0, os.getcwd())  # parent sets cwd to the tests dir
from test_multihost import build_ttt_batch, run_one_train_step

batch, module, params, args = build_ttt_batch()
mesh = make_mesh(mesh_spec)
B_local = batch["action"].shape[0] // nproc
local = jax.tree.map(lambda x: x[pid * B_local:(pid + 1) * B_local], batch)
new_params, loss = run_one_train_step(module, args, mesh, params, local)

leaves = [np.asarray(x) for x in jax.tree.leaves(new_params)]
np.savez(os.path.join(outdir, f"params_{pid}.npz"), loss=loss, *leaves)
"""


# Sequence-parallel plane across processes: masked ring attention with T
# sharded over an 'sp' axis spanning the 2-process global mesh — the K/V
# ring's ppermute hops cross process boundaries.  Inputs are seeded
# identically everywhere; each process contributes its local T rows via
# make_array_from_process_local_data, and the sharded output is
# all-gathered and dumped for comparison against the single-process
# einsum reference.
_RING_CHILD = r"""
import os, sys

port, pid, nproc, outdir = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from handyrl_tpu.ops import masked_ring_self_attention
from handyrl_tpu.parallel import init_distributed, make_mesh

init_distributed(
    {"coordinator_address": f"127.0.0.1:{port}", "num_processes": nproc, "process_id": pid}
)

sys.path.insert(0, os.getcwd())
from test_multihost import build_ring_inputs

q, k, v, key_mask, slopes, window = build_ring_inputs()
mesh = make_mesh({"sp": -1})
T = q.shape[1]
T_proc = T // nproc

def put(x, spec):
    sh = NamedSharding(mesh, spec)
    local = x[:, pid * T_proc:(pid + 1) * T_proc]
    return jax.make_array_from_process_local_data(sh, np.asarray(local))

qg = put(q, P(None, "sp", None, None))
kg = put(k, P(None, "sp", None, None))
vg = put(v, P(None, "sp", None, None))
mg = put(key_mask, P(None, "sp"))

out = masked_ring_self_attention(qg, kg, vg, mg, jax.numpy.asarray(slopes), mesh, window=window)
rep = jax.jit(lambda x: x, out_shardings=NamedSharding(mesh, P()))(out)
np.savez(os.path.join(outdir, f"ring_{pid}.npz"), out=np.asarray(jax.device_get(rep)))
"""


def build_ring_inputs():
    """Deterministic (q, k, v, key_mask, slopes, window) for the ring test —
    same values in every process (fixed PRNG keys, host numpy)."""
    import numpy as np

    rng = np.random.RandomState(99)
    B, T, H, D = 2, 32, 2, 8
    q = rng.randn(B, T, H, D).astype(np.float32)
    k = rng.randn(B, T, H, D).astype(np.float32)
    v = rng.randn(B, T, H, D).astype(np.float32)
    key_mask = (rng.rand(B, T) < 0.7).astype(np.float32)
    slopes = (2.0 ** -np.arange(1, H + 1)).astype(np.float32)
    return q, k, v, key_mask, slopes, 8


@pytest.mark.slow
def test_two_process_ring_attention(tmp_path):
    """Masked ring attention with the 'sp' axis spanning 2 processes must
    match the single-process einsum reference — the sequence-parallel
    plane's cross-host claim (its ppermute ring hops process boundaries)."""
    import numpy as np

    port = free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RING_CHILD, str(port), str(pid), "2", str(tmp_path)],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=300)[0].decode(errors="replace") for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"

    import jax

    from handyrl_tpu.ops.flash_attention import masked_attention_reference

    q, k, v, key_mask, slopes, window = build_ring_inputs()
    ref = np.asarray(
        masked_attention_reference(q, k, v, key_mask, slopes, window=window)
    )
    for pid in range(2):
        got = np.load(tmp_path / f"ring_{pid}.npz")["out"]
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-5)


def build_ttt_batch():
    """Deterministic TicTacToe batch + module + init params (seeded global
    RNGs: every caller that seeds the same way gets byte-identical data)."""
    import random as pyrandom

    import numpy as np

    pyrandom.seed(1234)
    np.random.seed(1234)

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, RandomModel, init_variables
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    cfg = normalize_args(
        # compaction off: the multi-process path skips it by design (all
        # processes must agree on global shapes), so the single-process
        # reference run must train the same uncompacted program
        {"env_args": {"env": "TicTacToe"},
         "train_args": {"batch_size": 4, "compact_padding": False}}
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]

    env = make_env(args["env"])
    module = env.net()
    variables = init_variables(module, env)
    model = InferenceModel(module, variables)
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))

    store = EpisodeStore(64)
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < 8:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(
            args["forward_steps"], args["burn_in_steps"], args["compress_steps"]
        )
        if w is not None:
            windows.append(w)
    return make_batch(windows, args), module, variables["params"], args


def run_one_train_step(module, args, mesh, params, local_batch):
    """One real TrainContext.train_step; returns (host params, loss).

    Params are re-laid-out replicated before the host fetch: under an
    'mp' mesh axis the updated kernels are SHARDED across the global
    devices, and in a multi-process run device_get of a partially
    non-addressable array fails — the jitted identity performs the
    all-gather (a no-op when already replicated)."""
    import jax
    import numpy as np

    from handyrl_tpu.parallel import TrainContext

    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(params)
    device_batch = ctx.put_batch(local_batch)
    state, metrics = ctx.train_step(state, device_batch, 1e-3)
    gathered = jax.jit(lambda t: t, out_shardings=ctx._replicated)(state["params"])
    host = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), gathered)
    return host, float(jax.device_get(metrics["total"]))


@pytest.mark.slow
def test_two_process_cpu_distributed(tmp_path):
    port = free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(port), str(pid), "2", str(tmp_path)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        outs.append(out.decode(errors="replace"))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"

    result = json.load(open(tmp_path / "result.json"))
    assert result["process_count"] == 2
    # global sum: 2 local rows x 3 cols of (pid+1) per process = 6*1 + 6*2
    assert abs(result["total"] - 18.0) < 1e-6
    assert (tmp_path / "noncoord_1.txt").exists()
    assert not (tmp_path / "noncoord_0.txt").exists()


def _two_process_train_and_compare(tmp_path, mesh_spec: str, exact_cross: bool):
    """Spawn 2 jax.distributed processes x 2 virtual devices running the
    REAL jitted sharded update on local batch shards under ``mesh_spec``,
    then assert (a) both processes end with the same params and (b) those
    params match a single-process update on the full batch (same math up
    to float reassociation — the sharded program's reduction order may
    differ, so the cross-process check is exact only for the replicated
    dp layout)."""
    import json

    import numpy as np

    port = free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _TRAIN_CHILD, str(port), str(pid), "2",
             str(tmp_path), mesh_spec],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out.decode(errors="replace"))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"

    dumps = [np.load(tmp_path / f"params_{pid}.npz") for pid in range(2)]
    keys = sorted(
        (k for k in dumps[0].files if k != "loss"),
        key=lambda s: int(s.split("_")[1]),  # arr_0..arr_N in leaf order
    )
    assert keys, "child dumped no param leaves"
    # identical across processes (same global program)
    for k in keys:
        if exact_cross:
            np.testing.assert_array_equal(dumps[0][k], dumps[1][k], err_msg=k)
        else:
            np.testing.assert_allclose(
                dumps[0][k], dumps[1][k], rtol=1e-6, atol=1e-8, err_msg=k
            )
    assert abs(float(dumps[0]["loss"]) - float(dumps[1]["loss"])) < 1e-6

    # and equal to the single-process update on the full batch — pinned to
    # the children's CPU backend (a TPU-backend parent would compare
    # bf16-matmul params against f32 XLA:CPU params and fail spuriously)
    import jax

    from handyrl_tpu.parallel import make_mesh

    batch, module, params, args = build_ttt_batch()
    ref_params, ref_loss = run_one_train_step(
        module, args, make_mesh({"dp": 1}), params, batch
    )
    ref_leaves = [np.asarray(x) for x in jax.tree.leaves(ref_params)]
    assert len(ref_leaves) == len(keys)
    changed = False
    init_leaves = [np.asarray(x) for x in jax.tree.leaves(params)]
    for k, ref, init in zip(keys, ref_leaves, init_leaves):
        np.testing.assert_allclose(
            dumps[0][k], ref, rtol=2e-4, atol=2e-6, err_msg=k
        )
        changed = changed or not np.array_equal(ref, init)
    assert changed, "update was a no-op: params identical to init"
    assert abs(float(dumps[0]["loss"]) - ref_loss) < 1e-4 * max(1.0, abs(ref_loss))


@pytest.mark.slow
def test_two_process_train_step(tmp_path):
    """TrainContext.train_step under jax.distributed on the replicated-dp
    layout: identical params on both processes (bit-exact) and match vs
    the single-process update (SURVEY §2.5's gradient-plane claim)."""
    _two_process_train_and_compare(tmp_path, '{"dp": -1}', exact_cross=True)


@pytest.mark.slow
def test_two_process_train_step_tensor_parallel(tmp_path):
    """The same claim with a tensor-parallel axis spanning the global mesh:
    dp=2 x mp=2 over 2 processes — kernels sharded over 'mp', batch over
    'dp', GSPMD's cross-process collectives doing both the gradient
    all-reduce and the tp gathers.  Params are all-gathered before the
    dump (see run_one_train_step)."""
    _two_process_train_and_compare(tmp_path, '{"dp": 2, "mp": 2}', exact_cross=False)


# ---------------------------------------------------------------------------
# PR 12: the distributed EPOCH LOOP — the full Learner under jax.distributed
# ---------------------------------------------------------------------------

# A real 2-process x 2-virtual-device Learner run, end to end: role
# assignment, per-process local batch shards through put_batch, the
# coordinator-broadcast epoch cadence, coordinator-only checkpoints and
# metrics, the cross-host health plane idling cleanly, and an agreed
# shutdown after `epochs` epochs with bit-identical params everywhere.
_LEARNER_CHILD = r"""
import json, os, sys

port, hport, pid, nproc, outdir = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]), sys.argv[5]
)
extra = json.loads(sys.argv[6]) if len(sys.argv) > 6 else {}
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % int(extra.get("devices", 2))
)
import jax

import numpy as np

from handyrl_tpu.config import normalize_args
from handyrl_tpu.parallel import init_distributed

dist = {
    "coordinator_address": f"127.0.0.1:{port}",
    "num_processes": nproc,
    "process_id": pid,
    "initialization_timeout": 120.0,
    "heartbeat_interval": 1.0,
    "heartbeat_timeout": float(extra.get("heartbeat_timeout", 15.0)),
    "collective_timeout": 300.0,
    "health_port": hport,
}
dist.update(extra.get("dist") or {})
init_distributed(dist)

shared_dir = bool(extra.get("shared_dir"))
train = {
    "batch_size": 4,
    "forward_steps": 4,
    "minimum_episodes": 6,
    "update_episodes": 6,
    "maximum_episodes": 100,
    "epochs": int(extra.get("epochs", 2)),
    "num_batchers": 0,           # threaded pipeline: no child forks in CI
    "batch_pipeline": "thread",
    "eval_rate": 0.2,
    "mesh": {"dp": -1},          # 4 global devices, replicated params
    "worker": {"num_parallel": 2},
    "restart_epoch": int(extra.get("restart_epoch", 0)),
    "model_dir": os.path.join(outdir, "models" if shared_dir else f"models_{pid}"),
    "metrics_path": os.path.join(
        outdir, "metrics.jsonl" if shared_dir else f"metrics_{pid}.jsonl"
    ),
    "distributed": dist,
}
train.update(extra.get("train") or {})
args = normalize_args(
    {"env_args": {"env": extra.get("env", "TicTacToe")}, "train_args": train}
)

from handyrl_tpu.runtime.learner import Learner

learner = Learner(args)
code = learner.run()
leaves = [np.asarray(x) for x in jax.tree.leaves(learner.trainer.params_host())]
np.savez(os.path.join(outdir, f"final_{pid}{extra.get('tag', '')}.npz"), *leaves)
with open(os.path.join(outdir, f"done_{pid}{extra.get('tag', '')}.json"), "w") as f:
    json.dump(
        {"code": code, "model_epoch": learner.model_epoch,
         "steps": int(learner.trainer.steps)}, f
    )
# synchronized coordination-service disconnect (what train_main does): an
# unsynchronized atexit shutdown trips the service's own heartbeat
# timeout and SIGABRTs the slower rank
from handyrl_tpu.parallel.distributed import shutdown_distributed

shutdown_distributed()
# Learner.run() has joined what it started that calls jax (the trainer, the
# batch pipeline's loops, the engine's serve loop, the rollout plane): the
# interpreter is not torn down with one of them inside a jax call ("FATAL:
# exception not rethrown", rc -6, was 2 of 6 runs; ROADMAP D14(a), PR 47)
sys.exit(code)
"""


def _spawn_learners(tmp_path, extra=None, env_extra=None, nproc=2, log_files=False):
    port, hport = free_port(), free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if env_extra:
        env.update(env_extra)
    blob = json.dumps(extra or {})
    if log_files:
        # unbounded-duration children (the host-loss e2es kill or outlive
        # them) must not block on a full stdout PIPE; unbuffered so the
        # poll loops see lines as they are printed
        env["PYTHONUNBUFFERED"] = "1"
    procs = []
    for pid in range(nproc):
        stdout = (
            open(os.path.join(str(tmp_path), f"learner_{pid}.log"), "wb")
            if log_files
            else subprocess.PIPE
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _LEARNER_CHILD, str(port), str(hport),
                 str(pid), str(nproc), str(tmp_path), blob],
                env=env,
                stdout=stdout,
                stderr=subprocess.STDOUT,
            )
        )
    return procs


def test_two_process_learner_epoch_loop(tmp_path):
    """Acceptance pin (non-slow, multihost CI step): a REAL 2-process
    Learner run completes 4 epochs under jax.distributed with params
    bit-identical on both processes, checkpoints/metrics written only by
    the coordinator, and a clean exit-0 shutdown on every rank.

    The run is TRACE-ENABLED (observability acceptance): each rank must
    write its own span file whose Perfetto export round-trips, and the
    coordinator's metrics.jsonl must carry rank_* aggregates covering
    BOTH ranks — the follower's snapshots arrive over the heartbeat
    relay, since PR 12 made metrics coordinator-only."""
    import numpy as np

    # generous heartbeat bound: this test pins the lockstep loop, not
    # detection latency, and a CI box under full-suite load can starve a
    # health thread for several seconds at a stretch
    # The follower's snapshot of an epoch rides its NEXT beat, so a record
    # folds both ranks only if a beat fell between two boundaries.  With the
    # children's 1 s interval and two epochs 0.4 s apart (a quiet box) none
    # did and the last record counted one rank; a loaded box stretched the
    # epochs and passed.  Beat every 0.1 s and run four epochs: three
    # boundaries follow the follower's first snapshot.
    procs = _spawn_learners(tmp_path, extra={
        "epochs": 4,
        "heartbeat_timeout": 45.0,
        "dist": {"heartbeat_interval": 0.1},
        "train": {"trace": {
            "enabled": True,
            "path": str(tmp_path / "trace.jsonl"),
            "flush_interval": 0.2,
        }},
    })
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    codes = [p.returncode for p in procs]
    assert codes == [0, 0], "".join(
        f"\n---- rank {i} rc={codes[i]} ----\n{out}" for i, out in enumerate(outs)
    )

    done = [json.load(open(tmp_path / f"done_{pid}.json")) for pid in range(2)]
    for d in done:
        assert d["code"] == 0
        assert d["model_epoch"] >= 2
        assert d["steps"] > 0
    # every process ran the SAME number of agreed steps
    assert done[0]["steps"] == done[1]["steps"]

    # bit-identical params on both processes (dp layout: exact)
    dumps = [np.load(tmp_path / f"final_{pid}.npz") for pid in range(2)]
    keys = sorted(dumps[0].files, key=lambda s: int(s.split("_")[1]))
    assert keys and dumps[1].files
    for k in keys:
        np.testing.assert_array_equal(dumps[0][k], dumps[1][k], err_msg=k)

    # exactly one writer: the coordinator owns checkpoints + metrics
    assert (tmp_path / "models_0" / "latest.ckpt").exists()
    assert (tmp_path / "models_0" / "MANIFEST.json").exists()
    assert (tmp_path / "metrics_0.jsonl").exists()
    assert not (tmp_path / "models_1").exists() or not any(
        (tmp_path / "models_1").iterdir()
    ), "non-coordinator wrote checkpoint files"
    assert not (tmp_path / "metrics_1.jsonl").exists(), "non-coordinator wrote metrics"
    records = [
        json.loads(l) for l in open(tmp_path / "metrics_0.jsonl") if l.strip()
    ]
    assert len(records) >= 2
    assert records[-1].get("dist_processes") == 2
    assert records[-1].get("dist_peer_loss_drains") == 0

    # every record carries the timestamp seam (the plot scripts' time axis)
    assert all("ts" in r and "t_mono" in r for r in records)

    # cross-host visibility (acceptance): some boundary record folds BOTH
    # ranks — the follower's per-epoch snapshot rode a heartbeat and the
    # coordinator aggregated it.  The first boundary may legitimately
    # precede the follower's first beat; a full run must not
    full = [r for r in records if r.get("rank_reports") == 2]
    assert full, [
        {k: v for k, v in r.items() if k.startswith("rank_")} for r in records
    ]
    last = full[-1]
    assert last["rank_missing_reports"] == 0
    assert last["rank_steps_min"] > 0
    assert last["rank_train_steps_per_sec_min"] > 0

    # trace-enabled run: one span file per rank (rank 1 derives its own
    # path), both parseable, and the merged Perfetto export round-trips
    from handyrl_tpu.utils.trace import read_trace

    trace0 = read_trace(str(tmp_path / "trace.jsonl"))
    trace1 = read_trace(str(tmp_path / "trace.rank1.jsonl"))
    names0 = {r["name"] for r in trace0}
    assert "train_step" in names0, sorted(names0)
    assert "cadence.agree_step" in names0, sorted(names0)
    assert "checkpoint.save" in names0, sorted(names0)
    assert {r["name"] for r in trace1} & {"cadence.agree_step", "train_step"}
    assert any(r["name"] == "health.heartbeat" for r in trace1)
    scripts = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
    )
    sys.path.insert(0, scripts)
    try:
        from trace_export import export_chrome
    finally:
        sys.path.remove(scripts)
    out = export_chrome([trace0, trace1])
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    n_spans = sum(
        1 for recs in (trace0, trace1) for r in recs
        if r["name"] != "__trace_meta__"
    )
    assert len(xs) == n_spans and n_spans > 0
    assert {e["pid"] for e in xs} == {0, 1}  # both ranks on one timeline


# the resume-epoch broadcast (the non-coordinator auto-resume fix): the
# coordinator's manifest verdict must reach every process — rank 1 gets a
# DIFFERENT (empty) model_dir, so only the broadcast can tell it epoch 3
_RESUME_CHILD = r"""
import json, os, sys

port, pid, nproc, outdir = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax

from handyrl_tpu.parallel import broadcast_resume_epoch, init_distributed, is_coordinator
from handyrl_tpu.runtime.checkpoint import latest_verified_epoch

init_distributed(
    {"coordinator_address": f"127.0.0.1:{port}", "num_processes": nproc, "process_id": pid}
)
model_dir = os.path.join(outdir, "models_0" if is_coordinator() else f"models_{pid}")
local = latest_verified_epoch(model_dir) if is_coordinator() else 0
agreed = broadcast_resume_epoch(local)
with open(os.path.join(outdir, f"resume_{pid}.json"), "w") as f:
    json.dump({"local": local, "agreed": agreed}, f)
"""


def test_resume_epoch_broadcast_two_process(tmp_path):
    """Satellite pin: runtime/learner.py used to resolve
    latest_verified_epoch only on the coordinator, leaving other ranks at
    model_epoch 0.  The coordinator's verdict must be broadcast: rank 1's
    model_dir is EMPTY here, yet it must agree on the coordinator's
    verified epoch 3."""
    import numpy as np

    from handyrl_tpu.runtime.checkpoint import save_epoch_snapshot

    coord_dir = tmp_path / "models_0"
    params = {"w": np.arange(6, dtype=np.float32)}
    for epoch in (1, 3):
        save_epoch_snapshot(str(coord_dir), epoch, params, dict(params), epoch * 10)
    (tmp_path / "models_1").mkdir()

    port = free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RESUME_CHILD, str(port), str(pid), "2", str(tmp_path)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=240)[0].decode(errors="replace") for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"
    r0 = json.load(open(tmp_path / "resume_0.json"))
    r1 = json.load(open(tmp_path / "resume_1.json"))
    assert r0 == {"local": 3, "agreed": 3}
    assert r1 == {"local": 0, "agreed": 3}, "coordinator's verdict did not reach rank 1"


def test_init_distributed_timeout_is_loud(tmp_path):
    """Satellite pin: a dead/mis-addressed coordinator must fail startup
    within distributed.initialization_timeout with an error naming the
    coordinator address — never hang forever."""
    script = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
from handyrl_tpu.parallel import init_distributed
t0 = time.monotonic()
try:
    init_distributed({
        "coordinator_address": "127.0.0.1:1",  # nothing listens on port 1
        "num_processes": 2,
        "process_id": 1,
        "initialization_timeout": 5.0,
    })
except RuntimeError as exc:
    msg = str(exc)
    assert "127.0.0.1:1" in msg, msg
    assert "initialization_timeout" in msg, msg
    print("LOUD-TIMEOUT-OK %.1fs" % (time.monotonic() - t0))
    sys.exit(0)
print("no error raised")
sys.exit(1)
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True, timeout=180
    )
    text = out.stdout.decode(errors="replace") + out.stderr.decode(errors="replace")
    assert out.returncode == 0, text
    assert "LOUD-TIMEOUT-OK" in text


@pytest.mark.slow
def test_sentinel_rollback_is_bit_coherent_across_processes(tmp_path):
    """Tentpole (c) pin: a sentinel rollback under jax.distributed must
    leave every process on the SAME verified snapshot.  Rank 1 runs with
    its own EMPTY model_dir — before the rollback agreement + params
    broadcast it would scan that empty dir, keep its diverged params, and
    silently break the bit-identical invariant while the coordinator
    rolled back."""
    import numpy as np

    procs = _spawn_learners(
        tmp_path,
        extra={
            "epochs": 4,
            "heartbeat_timeout": 45.0,  # pinning rollback coherence, not bounds
            "train": {"sentinel_rollback_after": 2},
        },
        # lr poisoned with NaN from SGD step 10 ONWARD on every rank (the
        # step counter is cadence-agreed, so the streak is identical; a
        # bounded window could be reset by a clean tail step before the
        # epoch-end threshold check — the test_sentinel e2e pattern)
        env_extra={"HANDYRL_FAULT_NAN_AT_STEP": "10:1000000"},
    )
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child rc={p.returncode}:\n{out}"

    records = [
        json.loads(l) for l in open(tmp_path / "metrics_0.jsonl") if l.strip()
    ]
    last = records[-1]
    assert last.get("sentinel_skipped_steps", 0) >= 2, outs[0]
    assert last.get("sentinel_rollbacks", 0) >= 1, outs[0]
    assert "rolled back to verified epoch" in outs[0]

    dumps = [np.load(tmp_path / f"final_{pid}.npz") for pid in range(2)]
    keys = sorted(dumps[0].files, key=lambda s: int(s.split("_")[1]))
    assert keys
    for k in keys:
        np.testing.assert_array_equal(dumps[0][k], dumps[1][k], err_msg=k)


def test_init_distributed_retry_is_real(monkeypatch):
    """The backoff-retry around jax.distributed.initialize must reset the
    half-initialized global state between attempts: jax assigns
    global_state.client BEFORE connect(), so without the reset every
    retry dies instantly on 'should only be called once' and the loop
    absorbs nothing."""
    import jax
    from jax._src.distributed import global_state

    from handyrl_tpu.parallel import distributed as D

    # the reset helper clears a poisoned state even when the client
    # object refuses a clean shutdown
    class _Stuck:
        def shutdown(self):
            raise RuntimeError("never connected")

    monkeypatch.setattr(global_state, "client", _Stuck(), raising=False)
    D._reset_half_initialized_state()
    assert global_state.client is None

    # ...and the init loop really reaches a second attempt
    attempts = []

    def fake_initialize(**kwargs):
        attempts.append(kwargs)
        if len(attempts) == 1:
            raise RuntimeError("UNAVAILABLE: connect failed")

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    rank = D.init_distributed(
        {
            "coordinator_address": "127.0.0.1:12345",
            "num_processes": 2,
            "process_id": 0,  # rank 0: no TCP pre-flight
            "initialization_timeout": 30.0,
        }
    )
    assert rank == 0
    assert len(attempts) == 2


def test_await_proceed_returns_delivered_verdict_after_stop():
    """The learner's shutdown path is proceed(stop) immediately followed
    by trainer.stop(); when stop_event wins that race the delivered
    verdict must STILL surface so the final agree_stop broadcast is
    dispatched — swallowing it abandons every follower inside the
    collective until the watchdog exits them 75 out of a clean run
    (reproduced under load before the fix)."""
    import queue as queue_mod
    import threading
    from types import SimpleNamespace

    from handyrl_tpu.runtime.trainer import Trainer

    t = SimpleNamespace(
        stop_event=threading.Event(), _proceed_queue=queue_mod.Queue(maxsize=1)
    )
    t._proceed_queue.put(True)
    t.stop_event.set()  # stop() already landed
    assert Trainer._await_proceed(t) is True

    t2 = SimpleNamespace(
        stop_event=threading.Event(), _proceed_queue=queue_mod.Queue(maxsize=1)
    )
    t2.stop_event.set()
    assert Trainer._await_proceed(t2) is None  # no verdict: no broadcast


def test_shutdown_coherent_gates_the_distributed_shutdown_barrier():
    """train_main only joins the synchronized jax.distributed.shutdown
    barrier when every rank will reach it: a clean finish or a cadence-
    AGREED drain.  After a follower-LOCAL drain the peers never join the
    barrier (they are still training, or leaving via os._exit), so waiting
    in it ends in the coordination service's SIGABRT instead of the
    promised exit 75 (docs/fault_tolerance.md, one-rank SIGTERM row)."""
    from types import SimpleNamespace

    from handyrl_tpu.runtime.learner import Learner

    coherent = Learner.shutdown_coherent.fget

    def state(nprocs, drain_requested, drain_agreed):
        return SimpleNamespace(
            _dist_nprocs=nprocs,
            _drain_requested=drain_requested,
            trainer=SimpleNamespace(drain_agreed=drain_agreed),
        )

    assert coherent(state(1, True, False))   # single-process: shutdown no-ops
    assert coherent(state(2, False, False))  # clean agreed finish
    assert coherent(state(2, True, True))    # coordinator drain, agreed by all
    assert not coherent(state(2, True, False))  # follower-local drain


# ---------------------------------------------------------------------------
# PR 12: host-loss e2es — the cross-host health plane under real process death
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_host_loss_kill_rank1_drain_exit75_and_resume(tmp_path):
    """Acceptance pin (slow leg): HANDYRL_FAULT_KILL_PROCESS_AT_EPOCH
    kills rank 1 at its first published epoch.  The surviving coordinator
    must detect the loss within the heartbeat bound (no indefinite
    collective hang), drain-save a manifest-verified checkpoint, and exit
    75; a relaunch of both ranks with restart_epoch: -1 then auto-resumes
    every process from that checkpoint and finishes cleanly."""
    from handyrl_tpu.runtime.checkpoint import latest_verified_epoch

    procs = _spawn_learners(
        tmp_path,
        extra={"epochs": 8, "shared_dir": True, "heartbeat_timeout": 6.0},
        env_extra={"HANDYRL_FAULT_KILL_PROCESS_AT_EPOCH": "1:1"},
    )
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    # rank 1 died hard by injection
    assert procs[1].returncode == 1, f"rank1 rc={procs[1].returncode}:\n{outs[1]}"
    assert "HANDYRL_FAULT_KILL_PROCESS_AT_EPOCH" in outs[1]
    # the survivor detected the loss, drain-saved, exited EX_TEMPFAIL
    assert procs[0].returncode == 75, f"rank0 rc={procs[0].returncode}:\n{outs[0]}"
    assert "host fault" in outs[0] and "peer process 1 lost" in outs[0], outs[0]
    assert "drain checkpoint" in outs[0], outs[0]
    drained = latest_verified_epoch(str(tmp_path / "models"))
    assert drained >= 1, "no verified drain checkpoint on disk"
    # the final pre-exit metrics record carries the dist_* event counters
    records = [
        json.loads(l) for l in open(tmp_path / "metrics.jsonl") if l.strip()
    ]
    assert records[-1].get("dist_peer_loss_drains", 0) >= 1

    # relaunch both ranks: every process must resume the SAME verified
    # epoch (coordinator scan + broadcast) and run to a clean finish
    procs = _spawn_learners(
        tmp_path,
        extra={"epochs": drained + 1, "shared_dir": True,
               "restart_epoch": -1, "tag": "_resumed"},
    )
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"relaunch rc={p.returncode}:\n{out}"
        assert f"auto-resume (restart_epoch: -1): epoch {drained}" in out, out
    done = [json.load(open(tmp_path / f"done_{pid}_resumed.json")) for pid in range(2)]
    for d in done:
        assert d["model_epoch"] >= drained + 1


@pytest.mark.slow
def test_coordinator_death_survivor_exits_loudly(tmp_path):
    """Acceptance pin (slow leg): when the COORDINATOR dies, the follower
    must exit loudly within the bound — never hang in the next collective.

    Two loud paths exist, and which one wins is a race the follower must
    survive either way: jax's own coordination-service client usually sees
    the leader's gRPC socket close within milliseconds and terminates the
    process with a fatal abort naming the leader death; the health plane's
    heartbeat bound (exit 75, ``host fault (coordinator_loss)``) covers
    the case the service cannot see — a coordinator host that wedges or
    partitions while its sockets stay up (pinned socket-free in
    tests/test_health.py, where the client clock drives the timeout).
    Either way: nonzero within the bound, a line naming the coordinator,
    no hang — which is the acceptance claim."""
    procs = _spawn_learners(
        tmp_path,
        extra={"epochs": 8, "shared_dir": True, "heartbeat_timeout": 6.0},
        env_extra={"HANDYRL_FAULT_KILL_PROCESS_AT_EPOCH": "1:0"},
    )
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    assert procs[0].returncode == 1, f"rank0 rc={procs[0].returncode}:\n{outs[0]}"
    assert "HANDYRL_FAULT_KILL_PROCESS_AT_EPOCH" in outs[0]
    rc1 = procs[1].returncode
    assert rc1 != 0 and rc1 is not None, f"follower exited 0:\n{outs[1]}"
    loud_health = "host fault" in outs[1] and "coordinator" in outs[1]
    loud_service = (
        "Terminating process because the JAX distributed service" in outs[1]
        or "coordination service" in outs[1]
    )
    assert loud_health or loud_service, (
        f"follower exit (rc={rc1}) was not loud about the coordinator:\n{outs[1]}"
    )


# ---------------------------------------------------------------------------
# PR 19: pod-slice — device planes + the actor-host tier under jax.distributed
# ---------------------------------------------------------------------------


def _assert_bit_identical_finals(tmp_path, nproc=2, tag=""):
    import numpy as np

    done = [
        json.load(open(tmp_path / f"done_{pid}{tag}.json")) for pid in range(nproc)
    ]
    for d in done:
        assert d["code"] == 0
        assert d["steps"] > 0
    assert len({d["steps"] for d in done}) == 1, done
    dumps = [np.load(tmp_path / f"final_{pid}{tag}.npz") for pid in range(nproc)]
    keys = sorted(dumps[0].files, key=lambda s: int(s.split("_")[1]))
    assert keys
    for k in keys:
        for d in dumps[1:]:
            np.testing.assert_array_equal(dumps[0][k], d[k], err_msg=k)


@pytest.mark.slow
def test_two_process_device_batch_pipeline_parity(tmp_path):
    """Tentpole acceptance pin (rung 1): `batch_pipeline: device` under a
    REAL 2-process run.  Each process stages its own host-born episodes
    into process-LOCAL device rings, samples its shard of the global batch
    on its own devices, and the shards meet the collective train step
    through the make_array_from_process_local_data seam — params must stay
    bit-identical on both ranks after 2 epochs, and the metrics must show
    the DEVICE pipeline actually ran (a silent fall-back to threads would
    pass the parity check while testing nothing)."""
    procs = _spawn_learners(tmp_path, extra={
        "epochs": 2,
        "heartbeat_timeout": 45.0,
        "train": {
            "batch_pipeline": "device",
            # TicTacToe turn mode on the device stage needs the observation
            # flag (windows carry all-player observation rows)
            "observation": True,
            "device_stage_lanes": 4,
            "device_stage_chunk": 8,
            "device_stage_slots": 64,
            "eval_rate": 0.0,
        },
    })
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    codes = [p.returncode for p in procs]
    assert codes == [0, 0], "".join(
        f"\n---- rank {i} rc={codes[i]} ----\n{out}" for i, out in enumerate(outs)
    )
    _assert_bit_identical_finals(tmp_path)
    records = [
        json.loads(l) for l in open(tmp_path / "metrics_0.jsonl") if l.strip()
    ]
    assert any(r.get("pipeline") == "device" for r in records), (
        [r.get("pipeline") for r in records], outs[0]
    )


@pytest.mark.slow
def test_two_process_split_plane_device_pipeline_e2e(tmp_path):
    """Tentpole acceptance pin (rung 1, the pod-slice shape itself): a
    REAL 2-process run where each rank's 4 virtual devices are carved
    2 + 2 — the leading pair joins the GLOBAL learner mesh (collective
    train step across hosts), the trailing pair is that rank's process-
    local actor plane running the streaming device rollout into its own
    DeviceReplay rings.  Per-rank RNGs are decorrelated (seed +
    1009*rank), so the ranks ingest DIFFERENT episodes and sample
    DIFFERENT local shards, yet the collective step must keep params
    bit-identical on both processes; the coordinator's metrics must carry
    the plane-health keys with both planes having actually worked."""
    procs = _spawn_learners(tmp_path, extra={
        "devices": 4,
        "env": "ParallelTicTacToe",
        "epochs": 2,
        "heartbeat_timeout": 45.0,
        "train": {
            "plane": "split",
            "actor_chips": 2,
            "param_refresh_updates": 2,
            # two ranks compiling rollout + ingest + the collective step
            # concurrently on shared host cores can silence the rollout
            # thread for minutes; the default 120s bound would degrade a
            # HEALTHY run split -> fused mid-test (seen in CI soak)
            "plane_stall_timeout": 600.0,
            "mesh": {"dp": -1},
            "turn_based_training": False,
            "observation": False,
            "batch_size": 8,
            "forward_steps": 4,
            "burn_in_steps": 0,
            "device_rollout_games": 8,
            "device_replay": True,
            "device_replay_slots": 64,
            "device_replay_k_steps": 16,
            "minimum_episodes": 20,
            "update_episodes": 30,
            "maximum_episodes": 400,
            "eval_rate": 0.0,
            "worker": {"num_parallel": 1},
        },
    })
    outs = [p.communicate(timeout=420)[0].decode(errors="replace") for p in procs]
    codes = [p.returncode for p in procs]
    assert codes == [0, 0], "".join(
        f"\n---- rank {i} rc={codes[i]} ----\n{out}" for i, out in enumerate(outs)
    )
    _assert_bit_identical_finals(tmp_path)
    records = [
        json.loads(l) for l in open(tmp_path / "metrics_0.jsonl") if l.strip()
    ]
    assert records[-1].get("dist_processes") == 2
    epoch_rows = [r for r in records if "plane_actor_busy_frac" in r]
    assert epoch_rows, f"no plane_* keys in metrics_0.jsonl: {records}"
    assert max(r["plane_actor_busy_frac"] for r in epoch_rows) > 0
    assert max(r["plane_xfer_bytes_per_sec"] for r in epoch_rows) > 0


# rung 2: a dedicated actor host — runs ONLY the data plane (streaming
# device rollout), ships records to the learner's plane gateway over TCP,
# polls versioned params back.  Deliberately outside jax.distributed.
_ACTOR_CHILD = r"""
import json, os, sys

outdir = sys.argv[1]
extra = json.loads(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % int(extra.get("devices", 2))
)
import jax

from handyrl_tpu.config import normalize_args
from handyrl_tpu.runtime.actor_host import actor_host_main

args = normalize_args(
    {"env_args": {"env": extra.get("env", "ParallelTicTacToe")},
     "train_args": extra["train"]}
)
actor_host_main(args)
"""


def _pod_slice_train(plane_port):
    # one learner process (2 virtual devices, fused plane, device replay)
    # + one actor host shipping over the gateway; the learner's OWN
    # streaming rollout keeps generating too, so losing the actor host
    # degrades throughput without stalling the cadence
    return {
        "turn_based_training": False,
        "observation": False,
        "batch_size": 8,
        "forward_steps": 4,
        "burn_in_steps": 0,
        "plane_stall_timeout": 600.0,  # compile storms are not stalls
        "device_rollout_games": 8,
        "device_replay": True,
        "device_replay_slots": 64,
        "device_replay_k_steps": 16,
        "minimum_episodes": 20,
        "update_episodes": 30,
        "maximum_episodes": 4000,
        "eval_rate": 0.0,
        "worker": {"num_parallel": 1},
        "mesh": {"dp": -1},
        # NO "distributed" key: the learner child's dist dict (which
        # carries actor_hosts + plane_port via extra["dist"]) must survive
        # the train.update() merge; _spawn_actor overrides it wholesale
    }


def _spawn_actor(tmp_path, plane_port, log_path, extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONUNBUFFERED"] = "1"  # the tests poll the log for lines
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    train = _pod_slice_train(plane_port)
    train["distributed"] = {
        # host part is what the actor dials; the port is the explicit
        # plane_port, so the coordinator port here is never used
        "coordinator_address": "127.0.0.1:6000",
        "num_processes": 1,
        "process_id": 0,
        "role": "actor",
        "plane_port": plane_port,
        "initialization_timeout": 180.0,
    }
    blob = json.dumps(dict(extra or {}, train=train))
    return subprocess.Popen(
        [sys.executable, "-c", _ACTOR_CHILD, str(tmp_path), blob],
        env=env,
        stdout=open(log_path, "wb"),
        stderr=subprocess.STDOUT,
    )


def _await_actor_connected(actor, log_path, learners, deadline_s=240):
    import time

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        text = log_path.read_bytes() if log_path.exists() else b""
        if b"connected to plane gateway" in text:
            return
        assert actor.poll() is None, (
            f"actor host died before connecting (rc={actor.returncode}):\n"
            + text.decode(errors="replace")
        )
        for p in learners:
            assert p.poll() is None, (
                f"learner exited (rc={p.returncode}) before the actor host "
                "connected"
            )
        time.sleep(0.5)
    raise AssertionError(
        "actor host never connected:\n"
        + (log_path.read_bytes().decode(errors="replace") if log_path.exists() else "")
    )


@pytest.mark.slow
def test_actor_host_loss_is_degradable(tmp_path):
    """Fault-matrix pin (rung 2, the degradable direction): killing a
    connected actor host must NOT gate the learner — the gateway logs the
    disconnect, bumps dist_actor_host_losses, and the learner's own
    rollout absorbs the game quota to a clean exit-0 finish."""
    plane_port = free_port()
    learners = _spawn_learners(
        tmp_path,
        nproc=1,
        log_files=True,  # no PIPE: nobody reads while we await the actor
        extra={
            "env": "ParallelTicTacToe",
            "epochs": 3,
            "heartbeat_timeout": 45.0,
            "dist": {"actor_hosts": 1, "plane_port": plane_port},
            "train": _pod_slice_train(plane_port),
        },
    )
    actor_log = tmp_path / "actor.log"
    actor = _spawn_actor(tmp_path, plane_port, actor_log)
    try:
        _await_actor_connected(actor, actor_log, learners)
    finally:
        actor.kill()
    actor.wait(timeout=60)
    try:
        learners[0].wait(timeout=420)
    finally:
        if learners[0].poll() is None:
            learners[0].kill()
    out = (tmp_path / "learner_0.log").read_bytes().decode(errors="replace")
    assert learners[0].returncode == 0, out
    records = [
        json.loads(l) for l in open(tmp_path / "metrics_0.jsonl") if l.strip()
    ]
    tiered = [r for r in records if "dist_actor_host_losses" in r]
    assert tiered, f"no actor-tier keys in metrics: {records}"
    assert tiered[-1]["dist_actor_host_losses"] >= 1, (tiered, out)
    # before the kill the host was COUNTED live at least once, or records
    # actually landed (either proves the tier was attached, not idle)
    assert (
        max(r["dist_actor_hosts"] for r in tiered) >= 1
        or "plane: records" in out
        or any(r.get("plane_xfer_bytes_per_sec", 0) > 0 for r in records)
    ), (tiered, out)


@pytest.mark.slow
def test_learner_loss_actor_exits_75(tmp_path):
    """Fault-matrix pin (rung 2, the loud direction): when the learner
    tier dies, a dedicated actor host must NOT spin generating against
    unowned params — its next gateway call raises, it announces the fault
    and exits 75 (EX_TEMPFAIL) for the supervisor to relaunch."""
    plane_port = free_port()
    learners = _spawn_learners(
        tmp_path,
        nproc=1,
        log_files=True,  # killed mid-run: must not block on a full PIPE
        extra={
            "env": "ParallelTicTacToe",
            "epochs": 1000,
            "heartbeat_timeout": 45.0,
            "dist": {"actor_hosts": 1, "plane_port": plane_port},
            "train": dict(_pod_slice_train(plane_port), maximum_episodes=10 ** 7),
        },
    )
    actor_log = tmp_path / "actor.log"
    actor = _spawn_actor(tmp_path, plane_port, actor_log)
    try:
        _await_actor_connected(actor, actor_log, learners)
        learners[0].kill()
        learners[0].wait(timeout=60)
        rc = actor.wait(timeout=420)
    finally:
        for p in learners + [actor]:
            if p.poll() is None:
                p.kill()
    out = actor_log.read_bytes().decode(errors="replace")
    assert rc == 75, f"actor rc={rc}:\n{out}"
    assert "plane gateway lost" in out, out
    assert "host fault (learner_loss)" in out, out
