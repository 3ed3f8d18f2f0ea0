"""Fault-injection suite: every failure mode the fault-tolerance layer
claims to survive is injected here and the recovery asserted
(docs/fault_tolerance.md is the failure matrix these tests pin down).

Checkpoint plane: a crash mid-save must leave the previous resume point
intact and verified; truncated/garbage files must be refused by digest,
with ``restart_epoch: -1`` falling back to the newest snapshot that still
verifies; resume round-trips Adam moments and the step count.

Batch-assembly plane: a SIGKILL'd shm batcher child is detected, its ring
slots reclaimed, and the child respawned — or, past the restart budget,
the pipeline degrades loudly to threaded batchers; either way batches
keep flowing within seconds and the events land in ``stats()``.

Actor plane: frame deadlines fire instead of blocking forever, one
stalled peer cannot wedge the hub for the others, a stalled entry
handshake cannot wedge later joins, and a severed gather socket makes the
worker machine rejoin through the entry port and resume episode flow with
no leaked actor thread and no learner hang on shutdown.

Fast tests run in the tier-1 sweep; the end-to-end injections are marked
``slow``.  CI runs the whole module standalone under ``-m faults``.
"""

import json
import os
import random
import signal
import socket
import threading
import time
from multiprocessing import shared_memory

import numpy as np
import pytest
from conftest import free_port

import handyrl_tpu.runtime.checkpoint as cp
from handyrl_tpu.config import normalize_args
from handyrl_tpu.runtime.connection import (
    FramedConnection,
    QueueCommunicator,
    accept_socket_connections,
    connect_socket_connection,
    send_recv,
)

pytestmark = pytest.mark.faults


def _tiny_args(extra=None, worker_extra=None):
    return normalize_args(
        {
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 4,
                "minimum_episodes": 10,
                "update_episodes": 12,
                "maximum_episodes": 100,
                "epochs": 1,
                "num_batchers": 1,
                "eval_rate": 0.2,
                "worker": {"num_parallel": 2},
                **(extra or {}),
            },
            "worker_args": worker_extra or {},
        }
    )


def _params(value: float):
    return {"w": np.full((3, 3), value, np.float32)}


def _state(value: float, steps: int):
    return {"params": _params(value), "steps": np.int32(steps)}


def _seed_snapshots(model_dir, epochs=(1, 2, 3)):
    for e in epochs:
        cp.save_epoch_snapshot(model_dir, e, _params(float(e)), _state(float(e), e * 10), e * 10)


# ---------------------------------------------------------------------------
# checkpoint plane
# ---------------------------------------------------------------------------


def test_crash_mid_save_keeps_previous_resume_point(tmp_path, monkeypatch):
    """Power loss during a save (simulated: fsync raises) must leave the
    previous epoch's files byte-intact and still digest-verified."""
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1,))

    def dying_fsync(fd):
        raise OSError("simulated power loss mid-write")

    monkeypatch.setattr(os, "fsync", dying_fsync)
    with pytest.raises(OSError):
        cp.save_epoch_snapshot(d, 2, _params(2.0), _state(2.0, 20), 20)
    monkeypatch.undo()

    assert cp.latest_verified_epoch(d) == 1
    restored = cp.load_verified_params(d, 1, _params(0.0))
    np.testing.assert_array_equal(restored["w"], _params(1.0)["w"])
    # the manifest never recorded epoch 2 — a half-written file cannot
    # become a resume candidate
    assert "2" not in cp.load_manifest(d)["epochs"]


def test_stray_tmp_files_never_break_resume(tmp_path):
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1, 2))
    # a crash between mkstemp and rename leaves exactly this
    with open(os.path.join(d, "3.ckpt.tmp.abc123"), "wb") as f:
        f.write(b"partial garbage")
    assert cp.latest_verified_epoch(d) == 2
    np.testing.assert_array_equal(
        cp.load_verified_params(d, 2, _params(0.0))["w"], _params(2.0)["w"]
    )


def test_truncated_snapshot_falls_back_to_older_verified(tmp_path):
    d = str(tmp_path)
    _seed_snapshots(d)
    with open(cp.model_path(d, 3), "r+b") as f:
        f.truncate(16)
    assert cp.latest_verified_epoch(d) == 2


def test_digest_mismatch_refused_and_skipped(tmp_path):
    """Same-size bit corruption: undetectable by existence/size checks,
    caught by CRC32.  Explicit loads refuse; auto-resume skips past."""
    d = str(tmp_path)
    _seed_snapshots(d)
    blob = open(cp.model_path(d, 3), "rb").read()
    with open(cp.model_path(d, 3), "wb") as f:
        f.write(bytes([blob[0] ^ 0xFF]) + blob[1:])
    assert cp.latest_verified_epoch(d) == 2
    with pytest.raises(cp.CheckpointError):
        cp.load_verified_params(d, 3, _params(0.0))


def test_corrupt_state_detected_by_manifest(tmp_path):
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1,))
    assert cp.verify_state(d, 1) is True
    with open(os.path.join(d, "state.ckpt"), "r+b") as f:
        f.truncate(8)
    assert cp.verify_state(d, 1) is False


def test_premanifest_layout_still_loads_and_auto_resumes(tmp_path):
    """Checkpoints from before the manifest existed (or with a deleted
    manifest) must keep loading — verification only refuses files it has
    a digest for — and auto-resume must fall back to the newest on-disk
    snapshot instead of silently restarting the run from scratch."""
    d = str(tmp_path)
    cp.save_params(cp.model_path(d, 3), _params(3.0))
    cp.save_params(cp.model_path(d, 4), _params(4.0))
    assert cp.verify_snapshot(d, 4) is None
    np.testing.assert_array_equal(
        cp.load_verified_params(d, 4, _params(0.0))["w"], _params(4.0)["w"]
    )
    # restart_epoch: -1 on an upgraded pre-manifest run dir picks the
    # newest unrecorded snapshot (an explicit epoch would load it too)
    assert cp.latest_verified_epoch(d) == 4


def test_manifest_recorded_corruption_never_resurrected_by_disk_scan(tmp_path):
    """The pre-manifest fallback must not undo verification: an epoch the
    manifest records as corrupt stays refused even if it is the newest
    file on disk."""
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1, 2))
    with open(cp.model_path(d, 2), "r+b") as f:
        f.truncate(16)
    assert cp.latest_verified_epoch(d) == 1


def test_corrupt_manifest_fails_loudly_and_save_self_heals(tmp_path):
    """An unparseable MANIFEST.json means corruption is PRESENT (manifest
    writes are atomic) — verification paths must refuse rather than
    silently load unverifiable files; the save path starts a fresh
    manifest so a healthy run keeps checkpointing and self-heals."""
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1, 2))
    with open(os.path.join(d, cp.MANIFEST_NAME), "w") as f:
        f.write("{ definitely not json")
    with pytest.raises(cp.CheckpointError):
        cp.latest_verified_epoch(d)
    with pytest.raises(cp.CheckpointError):
        cp.load_verified_params(d, 2, _params(0.0))
    # saving a new snapshot rebuilds the manifest and recovery resumes
    cp.save_epoch_snapshot(d, 3, _params(3.0), _state(3.0, 30), 30)
    assert cp.latest_verified_epoch(d) == 3


def test_retention_gc_keeps_newest_k_and_prunes_manifest(tmp_path):
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1, 2, 3, 4, 5))
    removed = cp.gc_snapshots(d, 2)
    assert removed == [1, 2, 3]
    assert sorted(cp.load_manifest(d)["epochs"]) == ["4", "5"]
    assert not os.path.exists(cp.model_path(d, 1))
    assert os.path.exists(cp.model_path(d, 5))
    assert os.path.exists(os.path.join(d, "latest.ckpt"))
    assert os.path.exists(os.path.join(d, "state.ckpt"))
    # 0 = keep all
    assert cp.gc_snapshots(d, 0) == []


def test_gc_never_collects_the_newest_verified_rollback_target(tmp_path):
    """GC x sentinel-rollback interplay: when every snapshot inside the
    retention window is corrupt, the newest VERIFIED epoch — the one the
    divergence sentinel would roll back to, and auto-resume's landing
    point — is PINNED even though it falls outside ``keep_checkpoints``.
    Collecting it would turn a one-epoch rollback into a from-scratch
    restart."""
    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1, 2, 3, 4, 5))
    # the newest two (the whole keep=2 window) rot on disk; the manifest
    # still records them, so verification is what must save epoch 3
    for e in (4, 5):
        with open(cp.model_path(d, e), "r+b") as f:
            f.write(b"\xff" * 16)
    assert cp.latest_verified_epoch(d) == 3
    removed = cp.gc_snapshots(d, 2)
    # 3 is pinned; the older unverified snapshots still go
    assert removed == [1, 2]
    assert os.path.exists(cp.model_path(d, 3))
    assert "3" in cp.load_manifest(d)["epochs"]
    # the rollback target still loads verified after the GC pass
    np.testing.assert_array_equal(
        cp.load_verified_params(d, 3, _params(0.0))["w"], _params(3.0)["w"]
    )
    # healthy directory: the pin is the newest kept snapshot anyway — GC
    # behavior is unchanged (no extra survivors)
    d2 = str(tmp_path / "healthy")
    _seed_snapshots(d2, epochs=(1, 2, 3, 4, 5))
    assert cp.gc_snapshots(d2, 2) == [1, 2, 3]


def test_gc_never_collects_epochs_the_serving_tier_is_routing(tmp_path):
    """GC x flywheel interplay (the serving analogue of the rollback-target
    pin above): SERVING.json publishes which epochs the serving tier is
    ROUTING (latest / staged candidate / displaced incumbent), and the
    learner's GC pass pins them — collecting the incumbent would turn a
    quality demote into a cold resurrection-from-nothing, and collecting a
    staged candidate would fail its promotion mid-gate."""
    from handyrl_tpu.flywheel import serving_pinned_epochs, write_serving_state

    d = str(tmp_path)
    _seed_snapshots(d, epochs=(1, 2, 3, 4, 5, 6, 7))
    # the serving tier routes latest=7 with candidate 2 staged and
    # incumbent 1 retained — both far outside the keep=2 window
    write_serving_state(d, latest=7, candidate=2, incumbent=1)
    pins = serving_pinned_epochs(d)
    assert pins == {7, 2, 1}
    removed = cp.gc_snapshots(d, 2, pin=pins)
    assert removed == [3, 4, 5]
    for e in (1, 2, 6, 7):
        assert os.path.exists(cp.model_path(d, e))
    # the incumbent (the sentinel's demote/rollback target) still loads
    # verified after the GC pass
    np.testing.assert_array_equal(
        cp.load_verified_params(d, 1, _params(0.0))["w"], _params(1.0)["w"]
    )
    # no state file / torn state degrades to the empty pin set, never raises
    assert serving_pinned_epochs(str(tmp_path / "absent")) == set()


def test_resume_roundtrip_preserves_adam_moments_and_steps(tmp_path):
    """The trainer contract behind every resume test: params + Adam
    moments + step count + lr EMA round-trip bit-exactly through the
    atomic snapshot, an epoch mismatch branches with a fresh optimizer,
    and a truncated state file degrades instead of raising."""
    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.parallel import make_mesh
    from handyrl_tpu.runtime.trainer import Trainer

    args = dict(_tiny_args()["train_args"])
    args["env"] = {"env": "TicTacToe"}
    env = make_env(args["env"])
    module = env.net()
    params = init_variables(module, env)["params"]
    mesh = make_mesh({"dp": 1})

    trainer = Trainer(args, module, params, mesh)
    trainer.state_host["steps"] = np.int32(77)
    trainer.data_cnt_ema = 123.5
    d = str(tmp_path)
    cp.save_epoch_snapshot(d, 1, trainer.params_host(), trainer.save_payload(1), 77)
    state_path = os.path.join(d, "state.ckpt")

    fresh = Trainer(args, module, params, mesh)
    assert fresh.load_state(state_path, expected_epoch=1) is True
    assert fresh.steps == 77
    assert fresh.data_cnt_ema == 123.5
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        trainer.state_host["opt_state"],
        fresh.state_host["opt_state"],
    )

    # epoch mismatch = branch, not resume
    other = Trainer(args, module, params, mesh)
    assert other.load_state(state_path, expected_epoch=2) is False

    # truncated state = fresh optimizer, never an exception
    with open(state_path, "r+b") as f:
        f.truncate(8)
    broken = Trainer(args, module, params, mesh)
    assert broken.load_state(state_path, expected_epoch=1) is False


@pytest.mark.slow
def test_learner_auto_resume_after_corruption(tmp_path, monkeypatch):
    """End to end: train 2 epochs, truncate the newest snapshot, restart
    with ``restart_epoch: -1`` — the learner resumes from epoch 1 (the
    newest VERIFIED snapshot) and keeps training."""
    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    learner = Learner(_tiny_args({"epochs": 2}))
    learner.run()
    assert learner.model_epoch == 2
    assert cp.latest_verified_epoch("models") == 2

    with open("models/2.ckpt", "r+b") as f:
        f.truncate(16)

    resumed = Learner(_tiny_args({"restart_epoch": -1, "epochs": 3}))
    assert resumed.model_epoch == 1, "auto-resume must land on the newest verified epoch"
    resumed.run()
    assert resumed.model_epoch == 3
    # the re-written epoch snapshots verify again
    assert cp.latest_verified_epoch("models") == 3


# ---------------------------------------------------------------------------
# batch-assembly plane
# ---------------------------------------------------------------------------


def _gen_store(n, targs, seed=0):
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables
    from handyrl_tpu.runtime.generation import Generator
    from handyrl_tpu.runtime.replay import EpisodeStore

    random.seed(seed)
    env = make_env({"env": "TicTacToe"})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env, seed=seed))
    gen = Generator(env, targs)
    models = {p: model for p in env.players()}
    gen_args = {"player": env.players(), "model_id": {p: 1 for p in env.players()}}
    store = EpisodeStore(1000)
    eps = []
    while len(eps) < n:
        ep = gen.generate(models, gen_args)
        if ep is not None:
            eps.append(ep)
    store.extend(eps)
    return store


class _HostCtx:
    """put_batch stub (mirrors tests/test_shm_pipeline.py)."""

    def put_batch(self, batch):
        import jax

        return jax.tree.map(np.array, batch)

    def put_batches(self, batches):
        import jax

        return [jax.tree.map(np.array, b) for b in batches]


def _shm_args(**over):
    raw = {"env_args": {"env": "TicTacToe"}, "train_args": over}
    return normalize_args(raw)["train_args"]


def test_sigkilled_batcher_child_is_respawned_and_batches_flow():
    """Acceptance: SIGKILL one shm batcher child mid-run -> batch flow
    resumes within 10 s, the death and respawn are visible in stats."""
    from handyrl_tpu.runtime.shm_batch import ShmBatchPipeline

    targs = _shm_args(batch_size=4, forward_steps=8, num_batchers=2,
                      batcher_max_restarts=3, batcher_stall_timeout=30.0)
    store = _gen_store(8, targs)
    stop = threading.Event()
    pipe = ShmBatchPipeline(targs, store, _HostCtx(), stop)
    pipe.start()
    try:
        assert pipe._fallback is None, "shm plane fell back before the injection"
        assert pipe.batch() is not None  # steady state reached

        victim = pipe._procs[0]
        os.kill(victim.pid, signal.SIGKILL)

        # flow must resume: drain well past every pre-kill buffer (device
        # queue depth 2 + up to n_slots filled slots) inside the 10 s SLO
        deadline = time.monotonic() + 10.0
        drained = 0
        while drained < 10 and time.monotonic() < deadline:
            assert pipe.batch() is not None, "pipeline died after child SIGKILL"
            drained += 1
        assert drained >= 10, f"only {drained} batches within 10s of the SIGKILL"

        # supervision notices within the same SLO (the drain above can
        # finish in well under one 0.25s supervision tick)
        while time.monotonic() < deadline:
            if pipe.stats()["batcher_deaths"] >= 1:
                break
            pipe.batch()  # keep the ring moving
            time.sleep(0.05)
        stats = pipe.stats()
        assert stats["batcher_deaths"] >= 1, "supervision missed the dead child"
        assert stats["batcher_restarts"] >= 1 or stats.get("batcher_fallback"), (
            "dead child neither respawned nor degraded"
        )
        # the respawned child is actually alive
        if not stats.get("batcher_fallback"):
            alive = [p for p in pipe._procs if p is not None and p.is_alive()]
            assert len(alive) == 2, "respawn did not restore the child pool"
    finally:
        stop.set()
        pipe.stop()
    for proc in pipe._procs:
        if proc is not None:
            proc.join(timeout=5)
            assert not proc.is_alive(), "orphaned batcher process"


def test_batcher_restart_budget_degrades_to_thread_pipeline():
    """Past ``batcher_max_restarts`` the shm plane must hand over to the
    threaded pipeline loudly — batches keep flowing, the mode flips, the
    shm segment is unlinked."""
    from handyrl_tpu.runtime.shm_batch import ShmBatchPipeline

    targs = _shm_args(batch_size=4, forward_steps=8, num_batchers=1,
                      batcher_max_restarts=0, batcher_stall_timeout=30.0)
    store = _gen_store(8, targs)
    stop = threading.Event()
    pipe = ShmBatchPipeline(targs, store, _HostCtx(), stop)
    pipe.start()
    shm_name = pipe._shm.name
    try:
        assert pipe._fallback is None
        assert pipe.batch() is not None
        os.kill(pipe._procs[0].pid, signal.SIGKILL)

        # batches may keep draining from pre-kill buffers while supervision
        # notices the death (throttled ticks); poll for the mode flip, then
        # prove continued flow THROUGH the fallback
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if pipe.stats()["mode"] == "thread":
                break
            assert pipe.batch() is not None, "no batches after the kill"
            time.sleep(0.05)
        stats = pipe.stats()
        assert stats["mode"] == "thread", "stats must expose the degraded mode"
        assert stats["batcher_deaths"] >= 1
        assert stats["batcher_fallback"] == 1.0
        for _ in range(3):
            assert pipe.batch() is not None, "fallback pipeline not producing"

        # the shm ring is fully torn down behind the fallback
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            try:
                probe = shared_memory.SharedMemory(name=shm_name)
                probe.close()
                time.sleep(0.2)
            except FileNotFoundError:
                break
        else:
            pytest.fail("shm segment still linked after degradation")
    finally:
        stop.set()
        pipe.stop()


# ---------------------------------------------------------------------------
# actor plane
# ---------------------------------------------------------------------------


def test_framed_recv_deadline_fires():
    port = free_port()

    def silent_server():
        for conn in accept_socket_connections(port=port, maxsize=1):
            time.sleep(2.0)  # accept, then say nothing
            conn.close()

    t = threading.Thread(target=silent_server, daemon=True)
    t.start()
    conn = connect_socket_connection("localhost", port, retry_seconds=5.0)
    t0 = time.monotonic()
    with pytest.raises(socket.timeout):
        conn.recv(timeout=0.3)
    assert time.monotonic() - t0 < 1.5
    conn.close()


def test_stalled_peer_does_not_wedge_other_peers():
    """One peer that stops reading (TCP window + its bounded send queue
    fill up) must be disconnected while the hub keeps serving everyone
    else — the single-shared-send-loop design this replaces wedged ALL
    peers on one stalled sendall."""
    port = free_port()
    hub = QueueCommunicator(send_queue_size=2)
    ready = threading.Event()
    ids = {}

    def server():
        for conn in accept_socket_connections(port=port, maxsize=2):
            hub.add_connection(conn)
        # learn which conn is which from a hello frame
        for _ in range(2):
            conn, data = hub.recv(timeout=10)
            ids[data] = conn
        ready.set()

    t = threading.Thread(target=server, daemon=True)
    t.start()
    stalled = connect_socket_connection("localhost", port, retry_seconds=5.0)
    healthy = connect_socket_connection("localhost", port, retry_seconds=5.0)
    stalled.send("stalled")
    healthy.send("healthy")
    assert ready.wait(timeout=10)

    # flood the stalled peer (which never reads) until its queue overflows
    big = np.zeros((1 << 18,), np.uint8)  # 256 KiB frames
    for _ in range(200):
        hub.send(ids["stalled"], big)
        if hub.connection_count() <= 1:
            break
    deadline = time.monotonic() + 10.0
    while hub.connection_count() > 1 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert hub.connection_count() == 1, "stalled peer was never torn down"

    # ...and the healthy peer is still served promptly
    hub.send(ids["healthy"], ("pong", 42))
    assert healthy.recv(timeout=5.0) == ("pong", 42)
    healthy.close()
    stalled.close()
    hub.shutdown()


def test_stalled_entry_handshake_does_not_block_joins():
    """Satellite: the entry thread recv()s with a HARD deadline — a
    client that connects and sends nothing, or drip-feeds one byte per
    gap (which a mere silence bound would keep alive forever), is
    dropped, and a well-behaved join right behind it completes."""
    from handyrl_tpu.runtime.server import WorkerServer

    entry_port, data_port = free_port(), free_port()
    args = {
        "env": {"env": "TicTacToe"},
        "worker": {
            "num_parallel": 2,
            "entry_port": entry_port,
            "data_port": data_port,
            "entry_timeout": 1.0,
            "heartbeat_interval": 0,
        },
    }
    server = WorkerServer(args, lambda req, data, timeout=None: None, None)
    server.run()
    try:
        # run() starts the entry thread, which binds and listens when it is
        # scheduled: a bare connect before that is refused (the failure
        # under six workers), so connect until the listener is up
        deadline = time.monotonic() + 10.0
        while True:
            try:
                trickler = socket.create_connection(("localhost", entry_port), timeout=5)
                break
            except ConnectionRefusedError:
                assert time.monotonic() < deadline, "the entry server never listened"
                time.sleep(0.02)
        stop_trickle = threading.Event()

        def trickle():
            # a huge frame length, then one byte every 0.4s (< the 1.0s
            # entry_timeout, so only an ABSOLUTE budget can shed it)
            try:
                trickler.sendall(b"\x00\xff\xff\xff")
                while not stop_trickle.is_set():
                    trickler.sendall(b"x")
                    stop_trickle.wait(0.4)
            except OSError:
                pass  # server dropped us: the desired outcome

        threading.Thread(target=trickle, daemon=True).start()
        # the trickler's connection is established, so it is ahead of this
        # one in the listener's accept queue: accepted first
        conn = connect_socket_connection("localhost", entry_port, retry_seconds=5.0)
        t0 = time.monotonic()
        reply = send_recv(conn, {"num_parallel": 2}, timeout=10.0)
        elapsed = time.monotonic() - t0
        assert reply["worker_args"]["base_worker_id"] == 0
        assert reply["env_args"] == {"env": "TicTacToe"}
        assert elapsed < 8.0, f"join waited {elapsed:.1f}s behind a trickled handshake"
        conn.close()
        stop_trickle.set()
        trickler.close()
    finally:
        server.shutdown_flag = True


@pytest.mark.slow
def test_severed_gather_socket_rejoins_and_training_finishes(tmp_path, monkeypatch):
    """Acceptance: sever every gather connection mid-run — the worker
    machine tears its session down (no actor thread survives it), rejoins
    through the entry port with backoff, episode flow resumes, training
    finishes every epoch, and shutdown drains cleanly."""
    from handyrl_tpu.runtime.learner import Learner
    from handyrl_tpu.runtime.server import RemoteWorkerCluster

    monkeypatch.chdir(tmp_path)
    entry_port, data_port = free_port(), free_port()
    args = _tiny_args(
        {
            "epochs": 3,
            "maximum_episodes": 200,
            "mesh": {"dp": 1},  # transport test, not a sharding test
            "worker": {
                "num_parallel": 2,
                "entry_port": entry_port,
                "data_port": data_port,
                "heartbeat_interval": 1.0,
                "socket_timeout": 15.0,
                "entry_timeout": 5.0,
            },
        },
        worker_extra={
            "server_address": "localhost",
            "num_parallel": 2,
            "entry_port": entry_port,
            "rejoin_backoff": 0.2,
            "rejoin_backoff_max": 1.0,
            "max_rejoins": 20,
            "entry_retry_seconds": 2.0,
        },
    )

    learner = Learner(args, remote=True)
    learner_thread = threading.Thread(target=learner.run, daemon=True)
    learner_thread.start()

    cluster = RemoteWorkerCluster(args["worker_args"])
    cluster_thread = threading.Thread(target=cluster.run, daemon=True)
    cluster_thread.start()

    # let the machine join and deliver, then cut every data connection
    deadline = time.time() + 120
    while learner.num_returned_episodes < 4 and time.time() < deadline:
        time.sleep(0.2)
    assert learner.num_returned_episodes >= 4, "worker machine never delivered"
    episodes_before = learner.num_returned_episodes
    severed = learner.worker.connections()
    assert severed, "no gather connections to sever"
    for conn in severed:
        learner.worker.disconnect(conn)

    learner_thread.join(timeout=420)
    assert not learner_thread.is_alive(), "learner hung after the severed socket"
    assert learner.num_returned_episodes > episodes_before, (
        "episode flow never recovered after the rejoin"
    )
    assert os.path.exists("models/3.ckpt")
    records = [json.loads(l) for l in open("metrics.jsonl")]
    assert len(records) >= 3

    # the cluster exits its supervision loop on the clean drain...
    cluster_thread.join(timeout=60)
    assert not cluster_thread.is_alive(), "worker cluster never exited after drain"
    # ...and no actor thread from ANY session (severed or final) leaks
    leaked = [
        t for t in threading.enumerate()
        if t.name.startswith("remote-actor-") and t.is_alive()
    ]
    assert not leaked, f"leaked actor threads: {[t.name for t in leaked]}"
