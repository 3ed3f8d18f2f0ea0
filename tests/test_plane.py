"""Split actor/learner device planes (runtime/plane.py, parallel/mesh.py).

The disaggregation contract, pinned on the virtual CPU mesh:

* `dispatch_serialized` keys its locks on the participating DEVICES —
  two programs on disjoint device sets must overlap (the whole split
  design rests on it), while overlapping sets keep the legacy mutual
  exclusion.
* `split_mesh` carves disjoint learner/actor meshes, learner keeping the
  device-list prefix.
* `PlaneParamCache` versions advance monotonically; `RecordTransfer`
  re-lays rollout records onto the learner mesh.
* End to end on 2 learner + 2 actor chips: the actor plane fills the
  learner plane's rings while the learner trains concurrently, loss
  stays finite, and the param versions the actor observes never rewind.
"""

import threading
import time

import jax
import numpy as np
import pytest
from conftest import free_port

from handyrl_tpu.config import normalize_args
from handyrl_tpu.parallel import make_mesh, split_mesh
from handyrl_tpu.parallel.mesh import dispatch_serialized
from handyrl_tpu.runtime.plane import PlaneParamCache, PlaneStats, RecordTransfer

pytestmark = pytest.mark.plane

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >= 4 (virtual) devices"
)
needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 (virtual) devices"
)


# ---------------------------------------------------------------- locks


def _enqueue_on(device):
    """Enqueue a trivial single-device program and return its async out."""
    x = jax.device_put(np.float32(1.0), device)
    return x + 1


@needs2
def test_disjoint_dispatches_overlap():
    """Two disjoint single-device dispatches must be in flight at once.

    Each call() blocks on a shared barrier BEFORE enqueueing: both
    threads can only pass it if dispatch_serialized admitted them
    concurrently.  Under the old global DISPATCH_LOCK the second thread
    would still be waiting to acquire when the first hits the barrier —
    the barrier times out and the test fails."""
    d0, d1 = jax.devices()[:2]
    barrier = threading.Barrier(2, timeout=30.0)
    out, errs = {}, []

    def run(name, dev):
        def call():
            barrier.wait()          # both inside their dispatch, or bust
            return _enqueue_on(dev)

        try:
            out[name] = dispatch_serialized(call, [dev])
        except Exception as exc:  # barrier timeout surfaces here
            errs.append(f"{name}: {exc!r}")

    threads = [
        threading.Thread(target=run, args=("a", d0)),
        threading.Thread(target=run, args=("b", d1)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert not errs, errs
    assert float(out["a"]) == 2.0 and float(out["b"]) == 2.0


def test_same_device_dispatches_still_serialize():
    """Overlapping device sets keep the mutual-exclusion guarantee: the
    in-dispatch intervals of two same-device calls never overlap."""
    dev = jax.devices()[0]
    spans = []

    def run():
        def call():
            t0 = time.perf_counter()
            time.sleep(0.05)
            r = _enqueue_on(dev)
            spans.append((t0, time.perf_counter()))
            return r

        dispatch_serialized(call, [dev])

    threads = [threading.Thread(target=run) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert len(spans) == 2
    (a0, a1), (b0, b1) = sorted(spans)
    assert a1 <= b0, f"same-device dispatches overlapped: {spans}"


@needs2
def test_multi_lock_acquisition_no_deadlock():
    """Opposite-order device sets ({d0,d1} vs {d1,d0}) must not deadlock:
    the registry acquires in canonical sorted order."""
    d0, d1 = jax.devices()[:2]
    done = []

    def run(devs):
        dispatch_serialized(lambda: _enqueue_on(devs[0]), devs)
        done.append(devs)

    threads = [
        threading.Thread(target=run, args=([d0, d1],)),
        threading.Thread(target=run, args=([d1, d0],)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert len(done) == 2


# ----------------------------------------------------------- split_mesh


@needs4
def test_split_mesh_partitions_devices():
    devices = jax.devices()[:4]
    learner, actor = split_mesh({"dp": 2}, 2, devices=devices)
    l_ids = [d.id for d in learner.devices.flat]
    a_ids = [d.id for d in actor.devices.flat]
    # disjoint, covering, learner keeps the prefix (device 0 stays the
    # coordinator/checkpoint owner)
    assert set(l_ids) & set(a_ids) == set()
    assert sorted(l_ids + a_ids) == [d.id for d in devices]
    assert l_ids == [d.id for d in devices[:2]]
    assert learner.shape.get("dp") == 2
    assert actor.shape == {"dp": 2}


def test_split_mesh_rejects_bad_actor_chips():
    devices = jax.devices()
    with pytest.raises(ValueError, match="at least one learner device"):
        split_mesh(None, len(devices), devices=devices)
    with pytest.raises(ValueError, match=">= 1"):
        split_mesh(None, 0, devices=devices)


# ------------------------------------------------------- config surface


def test_config_validates_plane():
    ok = normalize_args(
        {
            "env_args": {"env": "HungryGeese"},
            "train_args": {
                "plane": "split",
                "actor_chips": 2,
                "device_rollout_games": 16,
                "turn_based_training": False,
            },
        }
    )
    assert ok["train_args"]["plane"] == "split"

    with pytest.raises(ValueError, match="plane"):
        normalize_args(
            {"env_args": {"env": "HungryGeese"},
             "train_args": {"plane": "sideways"}}
        )
    # the actor plane generates with the on-device streaming rollout
    with pytest.raises(ValueError, match="device_rollout_games"):
        normalize_args(
            {"env_args": {"env": "HungryGeese"},
             "train_args": {"plane": "split"}}
        )
    with pytest.raises(ValueError, match="actor_chips"):
        normalize_args(
            {"env_args": {"env": "HungryGeese"},
             "train_args": {"plane": "split", "actor_chips": 0,
                            "device_rollout_games": 16}}
        )
    with pytest.raises(ValueError, match="param_refresh_updates"):
        normalize_args(
            {"env_args": {"env": "HungryGeese"},
             "train_args": {"plane": "split", "device_rollout_games": 16,
                            "param_refresh_updates": 0}}
        )


# ------------------------------------------------- cross-plane plumbing


def test_param_cache_versions_monotone():
    mesh = make_mesh({"dp": 1}, jax.devices()[-1:])
    cache = PlaneParamCache(mesh)
    params = {"w": np.ones((4, 4), np.float32)}
    with pytest.raises(RuntimeError, match="before first publish"):
        cache.latest()
    cache.publish(params, 0)
    cache.publish(params, 8)
    version, got = cache.latest()
    assert version == 8
    assert [d.id for d in jax.tree.leaves(got)[0].devices()] == [
        jax.devices()[-1].id
    ]
    with pytest.raises(ValueError, match="monotonically"):
        cache.publish(params, 8)
    with pytest.raises(ValueError, match="monotonically"):
        cache.publish(params, 3)
    assert cache.refreshes == 2
    assert cache.bytes_transferred == 2 * 4 * 4 * 4
    assert cache.lag(12) == 4
    assert cache.lag(8) == 0


@needs4
def test_record_transfer_moves_to_learner_mesh():
    devices = jax.devices()[:4]
    learner, actor = split_mesh({"dp": 2}, 2, devices=devices)
    from jax.sharding import NamedSharding, PartitionSpec

    # a (K, B, ...) record batch laid out lane-sharded on the ACTOR mesh
    rec = {
        "obs": jax.device_put(
            np.zeros((4, 8, 3), np.float32),
            NamedSharding(actor, PartitionSpec(None, "dp")),
        )
    }
    xfer = RecordTransfer(learner)
    moved = xfer(rec)
    got_ids = {d.id for d in moved["obs"].sharding.device_set}
    assert got_ids <= {d.id for d in learner.devices.flat}
    assert xfer.transfers == 1
    assert xfer.bytes_transferred == 4 * 8 * 3 * 4


def test_plane_stats_accumulate():
    stats = PlaneStats()
    stats.bump(actor_dispatches=1, param_lag_sum=3.0)
    stats.bump(actor_dispatches=1, actor_busy_s=0.5)
    snap = stats.snapshot()
    assert snap["actor_dispatches"] == 2
    assert snap["param_lag_sum"] == 3.0
    assert snap["actor_busy_s"] == 0.5


# ------------------------------------------------------ end-to-end smoke


@needs4
@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_split_plane_smoke():
    """2 learner + 2 actor chips: rollouts on the actor mesh fill the
    learner mesh's rings WHILE the learner trains, loss stays finite, and
    the param versions the actor observes advance monotonically."""
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.parallel import TrainContext
    from handyrl_tpu.runtime.device_replay import DeviceReplay
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn

    devices = jax.devices()[:4]
    learner_mesh, actor_mesh = split_mesh({"dp": 2}, 2, devices=devices)

    env = make_env({"env": "HungryGeese"})
    venv = env.vector_env()
    module = env.net()
    params = init_variables(module, env)["params"]
    cfg = normalize_args(
        {
            "env_args": {"env": "HungryGeese"},
            "train_args": {
                "turn_based_training": False,
                "observation": False,
                "batch_size": 4,
                "forward_steps": 4,
                "burn_in_steps": 0,
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]

    n_lanes, k_steps = 8, 8
    fn = build_streaming_fn(venv, module, n_lanes, k_steps, mesh=actor_mesh,
                            use_observe_mask=False)
    replay = DeviceReplay(venv, module, args, learner_mesh, n_lanes, slots=64)
    xfer = RecordTransfer(learner_mesh)
    cache = PlaneParamCache(actor_mesh)
    cache.publish(params, 0)

    vstate = venv.init(n_lanes, jax.random.PRNGKey(0))
    hidden = module.initial_state((n_lanes, venv.num_players))
    key = jax.random.PRNGKey(1)
    seen_versions = []

    def rollout():
        nonlocal vstate, hidden, key
        version, p = cache.latest()
        seen_versions.append(version)
        key, sub = jax.random.split(key)
        vstate, hidden, records = dispatch_serialized(
            lambda: fn(p, vstate, hidden, sub), actor_mesh
        )
        return replay.ingest(xfer(records))

    # prefill from the ACTOR plane until the learner rings are sampleable
    deadline = time.monotonic() + 300.0
    while replay.eligible_count() < args["batch_size"]:
        rollout()
        assert time.monotonic() < deadline, "rings never became sampleable"
    assert replay.eligible_count() >= args["batch_size"]

    ctx = TrainContext(module, args, learner_mesh)
    state = ctx.init_state(params)
    train = replay.train_fn(ctx, fused_steps=1)
    state, metrics = train(state, jax.random.PRNGKey(2), 1e-5)  # compile
    jax.block_until_ready(metrics["total"])

    # both planes inside one window: a producer thread keeps rolling out
    # (actor locks only) while this thread trains (learner locks only)
    stop = threading.Event()
    prod = {"dispatches": 0, "error": None}

    def producer():
        try:
            while not stop.is_set():
                rollout()
                prod["dispatches"] += 1
        except Exception as exc:
            prod["error"] = repr(exc)

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    steps = 0
    try:
        while prod["dispatches"] < 2 or steps < 3:
            tkey = jax.random.PRNGKey(100 + steps)
            state, metrics = train(state, tkey, 1e-5)
            jax.block_until_ready(metrics["total"])
            steps += 1
            cache.publish(state["params"], steps)
            assert time.monotonic() < deadline, (
                f"planes never both progressed: {steps=} {prod=}"
            )
            time.sleep(0.01)  # hand the unfair locks to the producer
    finally:
        stop.set()
        thread.join(timeout=120.0)
    assert prod["error"] is None, prod["error"]
    assert prod["dispatches"] >= 2          # actor plane ran concurrently
    assert steps >= 3                        # learner plane ran concurrently
    assert np.isfinite(float(jax.device_get(metrics["total"])))
    # the versions the actor observed never rewound, and refreshes landed
    assert seen_versions == sorted(seen_versions)
    assert seen_versions[-1] > seen_versions[0]


@needs4
@pytest.mark.slow
def test_learner_split_plane_end_to_end(tmp_path, monkeypatch):
    """The full Learner under `plane: split`: rollouts on the actor mesh
    feed the learner mesh's rings across two real epochs, and the
    plane-health keys land in metrics.jsonl."""
    import json
    import os

    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    args = normalize_args(
        {
            "env_args": {"env": "ParallelTicTacToe"},
            "train_args": {
                "plane": "split",
                "actor_chips": 2,
                "param_refresh_updates": 2,
                "mesh": {"dp": 2},
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
                "epochs": 2,
                "num_batchers": 1,
                "eval_rate": 0.0,
                "worker": {"num_parallel": 1},
            },
        }
    )
    learner = Learner(args)
    learner.run()

    assert os.path.exists("models/latest.ckpt")
    records = [json.loads(l) for l in open("metrics.jsonl")]
    assert records[-1]["steps"] > 0
    # the plane-health keys the soaks watch, from a real split run
    epoch_rows = [r for r in records if "plane_actor_busy_frac" in r]
    assert epoch_rows, f"no plane_* keys in metrics.jsonl: {records}"
    # cumulative counters are diffed per epoch: late epochs can be all
    # idle (episode budget met), but SOME epoch saw the actor plane work
    assert max(r["plane_actor_busy_frac"] for r in epoch_rows) > 0
    assert max(r["plane_xfer_bytes_per_sec"] for r in epoch_rows) > 0
    # the trainer surfaced its realized staleness + refresh count
    assert learner.trainer.stats.get("plane_param_refreshes", 0) > 0
    assert learner.trainer.param_cache.version > 0


# ------------------------------------------------- rung 2: cross-host wire


def _gw_dist(port):
    # explicit plane_port: the tests must not depend on health-port
    # derivation (and must not collide with anything else on the host)
    return {"coordinator_address": "127.0.0.1:6000", "plane_port": port}


def test_plane_wire_pack_round_trip():
    from handyrl_tpu.runtime.plane import _pack_tree, _unpack_tree

    tree = {
        "a": {"b": np.arange(6, dtype=np.float32).reshape(2, 3)},
        "c": np.array([1, -2], dtype=np.int8),
    }
    out = _unpack_tree(_pack_tree(tree))
    assert out["a"]["b"].dtype == np.float32
    np.testing.assert_array_equal(out["a"]["b"], tree["a"]["b"])
    np.testing.assert_array_equal(out["c"], tree["c"])
    # non-dict containers cannot round-trip the self-describing flattening
    with pytest.raises(ValueError, match="nested dicts"):
        _pack_tree({"a": [np.zeros(2)]})
    with pytest.raises(ValueError, match="separator"):
        _pack_tree({"a\x1fb": np.zeros(2)})


def test_plane_gateway_round_trip():
    """Records in, versioned params out, monotone versions, byte counts,
    and the clean-stop protocol — one gateway, one client, real sockets."""
    from handyrl_tpu.runtime.plane import PlaneClient, PlaneGateway

    dist = _gw_dist(free_port())
    received = []
    gw = PlaneGateway(dist, on_records=received.append)
    gw.start()
    client = PlaneClient(dist, timeout=10.0)
    try:
        gw.publish({"w": np.float32([1.0, 2.0])}, 10)
        assert client.connect(retry_for=10.0) == 10
        version, params = client.poll_params(have=-1)
        assert version == 10
        np.testing.assert_array_equal(params["w"], np.float32([1.0, 2.0]))
        # caught up: no payload rides the reply
        version, params = client.poll_params()
        assert version == 10 and params is None
        # records land in on_records BEFORE the reply (the ingest is the
        # ack), and the reply carries the poll hint
        recs = {"obs": np.zeros((4, 2), np.float32), "rew": np.ones((4,), np.float32)}
        assert client.ship_records(recs) == 10
        assert len(received) == 1
        np.testing.assert_array_equal(received[0]["obs"], recs["obs"])
        gw.publish({"w": np.float32([3.0, 4.0])}, 20)
        assert client.ship_records(recs) == 20
        version, fresh = client.poll_params()
        assert version == 20 and fresh is not None
        assert client.param_version == 20
        assert gw.record_batches == 2
        assert gw.bytes_in > 0 and gw.bytes_out > 0
        assert gw.bytes_transferred == gw.bytes_in + gw.bytes_out
        assert gw.lag(23) == 3
        with pytest.raises(ValueError, match="monotonically"):
            gw.publish({"w": np.zeros(2, np.float32)}, 20)
        assert gw.actor_hosts == 1 and gw.actor_hosts_seen == 1
        # run concluding: the next request is answered with a clean stop —
        # the client reports None (exit 0 path), NOT a counted loss
        gw.begin_stop()
        assert client.ship_records(recs) is None
        assert client.stopped
        client.close()
        deadline = time.time() + 5.0
        while gw.actor_hosts > 0 and time.time() < deadline:
            time.sleep(0.01)
        assert gw.actor_host_losses == 0
    finally:
        client.close()
        gw.stop()


def test_plane_gateway_counts_actor_host_loss():
    """Disconnect-after-hello while the run is live = a LOSS the books
    must show (dist_actor_host_losses); the gateway keeps serving."""
    from handyrl_tpu.runtime.plane import PlaneClient, PlaneGateway

    dist = _gw_dist(free_port())
    gw = PlaneGateway(dist, on_records=lambda r: None)
    gw.start()
    try:
        gw.publish({"w": np.zeros(2, np.float32)}, 1)
        client = PlaneClient(dist, timeout=10.0)
        client.connect(retry_for=10.0)
        client.close()   # vanish mid-run, no goodbye protocol exists
        deadline = time.time() + 5.0
        while gw.actor_host_losses == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert gw.actor_host_losses == 1
        assert gw.actor_hosts == 0
        # the gateway survives its lost producer: a new client connects
        client2 = PlaneClient(dist, timeout=10.0)
        assert client2.connect(retry_for=10.0) == 1
        client2.close()
    finally:
        gw.stop()
