"""Distributed actor plane tests: codec, framing, TCP workers, battle mode.

These exercise the multi-node surface the reference validates only
implicitly (SURVEY.md §4: the delta-sync replica test is the reference's
sole multi-node surrogate): the pickle-free wire codec, framed RPC over
real sockets, a full --train-server/--worker run on localhost, and the
network battle mode.
"""

import sys
import threading

import numpy as np
import pytest
from conftest import free_port

from handyrl_tpu.config import normalize_args
from handyrl_tpu.runtime import codec
from handyrl_tpu.runtime.connection import (
    FramedConnection,
    QueueCommunicator,
    accept_socket_connections,
    connect_socket_connection,
    send_recv,
)


def connect_retry(host: str, port: int) -> FramedConnection:
    return connect_socket_connection(host, port, retry_seconds=10.0)


# -- codec ------------------------------------------------------------------


def test_codec_roundtrip_scalars_and_containers():
    samples = [
        None,
        True,
        False,
        0,
        -(2**40),
        3.5,
        "hello ∑",
        b"\x00\xffbytes",
        [1, [2, "x"], None],
        (1, 2.5, "t"),
        {"a": 1, 0: "int-key", 1: {"nested": b"ok"}},
    ]
    for obj in samples:
        assert codec.loads(codec.dumps(obj)) == obj


def test_codec_roundtrip_numpy():
    arrays = [
        np.arange(12, dtype=np.int32).reshape(3, 4),
        np.random.randn(2, 3, 5).astype(np.float32),
        np.array(True),
        np.zeros((0, 7), np.float64),
    ]
    for arr in arrays:
        out = codec.loads(codec.dumps(arr))
        assert out.dtype == arr.dtype and out.shape == arr.shape
        np.testing.assert_array_equal(out, arr)
    # numpy scalars decay to python scalars
    assert codec.loads(codec.dumps(np.float32(2.5))) == 2.5
    assert codec.loads(codec.dumps(np.int64(7))) == 7


def test_codec_roundtrip_episode_like():
    episode = {
        "args": {"role": "g", "player": [0, 1], "model_id": {0: 3, 1: -1}},
        "steps": 9,
        "players": [0, 1],
        "outcome": {0: 1.0, 1: -1.0},
        "blocks": [b"compressed-block-1", b"compressed-block-2"],
    }
    assert codec.loads(codec.dumps(episode)) == episode


def _codec_corpus():
    return [
        None, True, False, 0, -(2**40), 2**62, 3.5, float("inf"), "hello ∑",
        b"\x00\xffbytes", bytearray(b"ba"), memoryview(b"mv"),
        [1, [2, "x"], None], (1, 2.5, "t"),
        {"a": 1, 0: "int-key", 1: {"nested": b"ok"}},
        np.arange(12, dtype=np.int32).reshape(3, 4),
        np.random.RandomState(3).randn(2, 3, 5).astype(np.float32),
        np.array(True), np.zeros((0, 7), np.float64),
        np.float32(2.5), np.int64(7), np.bool_(True),
        {"blocks": [b"z" * 300] * 4, "outcome": {0: 1.0, 1: -1.0}},
    ]


def test_codec_accel_loads_on_linux():
    """The C accelerator must actually build here — a silent fallback to
    pure Python on a platform with a compiler would hide a regression."""
    if sys.platform != "linux":
        pytest.skip("accelerator is best-effort off Linux")
    if codec._accel_disabled():
        pytest.skip("HANDYRL_NO_CODEC_ACCEL disables the accelerator")
    assert codec._accel is not None


def test_codec_impls_byte_identical_and_interop():
    """The C accelerator and the pure-Python codec must produce the SAME
    bytes (the format has one spec) and decode each other's output."""
    if codec._accel is None:
        pytest.skip("accelerator unavailable")
    for obj in _codec_corpus():
        b_py = codec.py_dumps(obj)
        b_c = codec._accel.dumps(obj)
        assert b_py == b_c, f"byte mismatch for {obj!r}"
        for decoded in (codec.py_loads(b_c), codec._accel.loads(b_py)):
            if isinstance(obj, np.ndarray):
                assert decoded.dtype == obj.dtype and decoded.shape == obj.shape
                np.testing.assert_array_equal(decoded, obj)
            elif isinstance(obj, (bytearray, memoryview)):
                assert decoded == bytes(obj)
            elif isinstance(obj, (np.bool_, np.integer, np.floating)):
                assert decoded == obj.item()
            else:
                assert decoded == obj


def test_codec_accel_malformed_frames():
    """Every strict prefix of a valid frame, and hostile headers, must
    surface as CodecError from BOTH implementations — connection receive
    loops drop the peer on CodecError; anything else would kill them."""
    impls = [codec.py_loads] + ([codec._accel.loads] if codec._accel else [])
    frame = codec.py_dumps(
        {"a": [1, 2.5, "s"], "arr": np.arange(6, dtype=np.float32).reshape(2, 3)}
    )
    for loads in impls:
        for i in range(len(frame)):
            with pytest.raises(codec.CodecError):
                loads(frame[:i])
        with pytest.raises(codec.CodecError):
            loads(frame + b"x")
        # hostile array header: junk dtype
        with pytest.raises(codec.CodecError):
            loads(b"a\x00\x00\x00\x02zz\x00\x00\x00\x01\x00\x00\x00\x05"
                  b"\x00\x00\x00\x04abcd")
        # raw-size / shape mismatch -> reshape error -> CodecError
        with pytest.raises(codec.CodecError):
            loads(b"a\x00\x00\x00\x03<f4\x00\x00\x00\x01\x00\x00\x00\x05"
                  b"\x00\x00\x00\x04abcd")
        # unknown tag
        with pytest.raises(codec.CodecError):
            loads(b"Z")


def test_codec_accel_depth_guard():
    """A deeply nested frame must fail bounded (CodecError), not smash the
    C stack: 'l' with count 1, nested a few thousand deep."""
    deep = b"l\x00\x00\x00\x01" * 4000 + b"N"
    impls = [codec.py_loads] + ([codec._accel.loads] if codec._accel else [])
    for loads in impls:
        with pytest.raises(codec.CodecError):
            loads(deep)
    if codec._accel is not None:
        lst = None
        for _ in range(4000):
            lst = [lst]
        with pytest.raises(codec.CodecError):
            codec._accel.dumps(lst)


def test_codec_oversized_length_is_codec_error():
    """Exception-type parity on >= 2**32 lengths: the C accelerator raises
    CodecError via enc_len_u32; the pure-Python fallback must match — an
    accelerated host and a fallback host have to fail the same way on the
    same oversized frame.  (Allocating a real 4 GiB payload is off the
    table on the 1-core host, so the length pack is exercised directly.)"""
    with pytest.raises(codec.CodecError):
        codec._pack_u32(2**32)
    with pytest.raises(codec.CodecError):
        codec._pack_u32(-1)
    assert codec._pack_u32(2**32 - 1) == b"\xff\xff\xff\xff"


def test_codec_impls_agree_on_random_structures():
    """Seeded structural fuzz: both implementations must byte-agree and
    round-trip on arbitrary nested payloads, not just the fixed corpus."""
    if codec._accel is None:
        pytest.skip("accelerator unavailable")
    rng = np.random.RandomState(1234)

    def gen(depth):
        kinds = ["int", "float", "str", "bytes", "none", "bool", "arr"]
        if depth < 3:
            kinds += ["list", "tuple", "dict"] * 2
        k = kinds[rng.randint(len(kinds))]
        if k == "int":
            return int(rng.randint(-(2**62), 2**62))
        if k == "float":
            return float(rng.randn() * 10 ** rng.randint(-8, 8))
        if k == "str":
            return "".join(chr(rng.randint(32, 0x2FF)) for _ in range(rng.randint(0, 12)))
        if k == "bytes":
            return bytes(rng.bytes(rng.randint(0, 32)))
        if k == "none":
            return None
        if k == "bool":
            return bool(rng.randint(2))
        if k == "arr":
            dt = [np.float32, np.float64, np.int32, np.int8, np.bool_][rng.randint(5)]
            shape = tuple(rng.randint(0, 4) for _ in range(rng.randint(0, 3)))
            # outer asarray AFTER the arithmetic: numpy returns a SCALAR
            # from 0-d math, and np scalars decay to python scalars on the
            # wire by design — this branch must produce a true ndarray
            # (including the 0-d case, the historical codec edge)
            return np.asarray(rng.randn(*shape) * 100).astype(dt)
        n = rng.randint(0, 5)
        if k == "list":
            return [gen(depth + 1) for _ in range(n)]
        if k == "tuple":
            return tuple(gen(depth + 1) for _ in range(n))
        return {f"k{i}": gen(depth + 1) for i in range(n)}

    def eq(a, b):
        if isinstance(a, np.ndarray):
            return (isinstance(b, np.ndarray) and a.dtype == b.dtype
                    and a.shape == b.shape and np.array_equal(a, b))
        if isinstance(a, (list, tuple)):
            return (type(a) is type(b) and len(a) == len(b)
                    and all(eq(x, y) for x, y in zip(a, b)))
        if isinstance(a, dict):
            return (isinstance(b, dict) and a.keys() == b.keys()
                    and all(eq(a[k], b[k]) for k in a))
        return a == b and type(a) is type(b)

    for _ in range(200):
        obj = gen(0)
        b_py = codec.py_dumps(obj)
        assert b_py == codec._accel.dumps(obj), repr(obj)
        assert eq(codec._accel.loads(b_py), codec.py_loads(b_py)), repr(obj)
        assert eq(codec.py_loads(b_py), obj), repr(obj)


def test_codec_fallback_forced(tmp_path):
    """HANDYRL_NO_CODEC_ACCEL=1 must leave the pure-Python codec fully
    functional (the accelerator is strictly optional) — checked in a
    subprocess because the dispatch is bound at import time."""
    import os
    import subprocess
    import sys as _sys

    script = (
        "from handyrl_tpu.runtime import codec\n"
        "assert codec._accel is None, 'accelerator loaded despite disable'\n"
        "assert codec.dumps is codec.py_dumps\n"
        "b = codec.dumps({'x': [1, 2.5, 'y']})\n"
        "assert codec.loads(b) == {'x': [1, 2.5, 'y']}\n"
        "print('fallback-ok')\n"
    )
    out = subprocess.run(
        [_sys.executable, "-c", script],
        env={**os.environ, "HANDYRL_NO_CODEC_ACCEL": "1",
             "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr.decode(errors="replace")
    assert b"fallback-ok" in out.stdout


def test_codec_rejects_unencodable():
    with pytest.raises(codec.CodecError):
        codec.dumps(object())
    with pytest.raises(codec.CodecError):
        codec.dumps(np.array([object()]))
    with pytest.raises(codec.CodecError):
        codec.loads(codec.dumps([1, 2]) + b"junk")


# -- framing + RPC over real sockets ---------------------------------------


def test_framed_send_recv_over_socket():
    port = free_port()
    server_obj = {"reply": np.ones((4, 4), np.float32), "n": 1}
    got = {}

    def server():
        for conn in accept_socket_connections(port=port, maxsize=1):
            got["req"] = conn.recv()
            conn.send(server_obj)
            conn.close()

    t = threading.Thread(target=server, daemon=True)
    t.start()
    conn = connect_retry("localhost", port)
    reply = send_recv(conn, ("args", None))
    conn.close()
    t.join(timeout=5)

    assert got["req"] == ("args", None)
    assert reply["n"] == 1
    np.testing.assert_array_equal(reply["reply"], np.ones((4, 4), np.float32))


def test_queue_communicator_echo():
    port = free_port()
    hub_box = {}

    def server():
        hub = QueueCommunicator()
        hub_box["hub"] = hub
        for conn in accept_socket_connections(port=port, maxsize=2):
            hub.add_connection(conn)
            break
        for _ in range(3):
            conn, data = hub.recv(timeout=5)
            hub.send(conn, ("echo", data))

    t = threading.Thread(target=server, daemon=True)
    t.start()
    conn = connect_retry("localhost", port)
    for i in range(3):
        assert send_recv(conn, i) == ("echo", i)
    conn.close()
    t.join(timeout=5)
    assert hub_box["hub"].connection_count() >= 0


# -- full remote training over localhost TCP --------------------------------


@pytest.mark.slow
def test_train_server_with_remote_worker(tmp_path, monkeypatch):
    import json
    import os

    from handyrl_tpu.runtime.learner import Learner
    from handyrl_tpu.runtime.server import worker_main

    monkeypatch.chdir(tmp_path)
    entry_port, data_port = free_port(), free_port()
    args = normalize_args(
        {
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 4,
                "minimum_episodes": 10,
                "update_episodes": 12,
                "maximum_episodes": 100,
                "epochs": 2,
                "num_batchers": 1,
                "eval_rate": 0.2,
                # 1-device mesh: this test exercises the TCP transport, not
                # sharding (test_end_to_end_training covers the 8-dev mesh).
                # On virtual CPU devices an 8-way all-reduce rendezvous can
                # starve when the two inference engines (learner + remote
                # machine, same process here) occupy the XLA CPU thread pool.
                "mesh": {"dp": 1},
                "worker": {"num_parallel": 2, "entry_port": entry_port, "data_port": data_port},
            },
            "worker_args": {
                "server_address": "localhost",
                "num_parallel": 2,
                "entry_port": entry_port,
            },
        }
    )

    learner = Learner(args, remote=True)
    learner_thread = threading.Thread(target=learner.run, daemon=True)
    learner_thread.start()

    worker_thread = threading.Thread(target=worker_main, args=(args,), daemon=True)
    worker_thread.start()

    learner_thread.join(timeout=300)
    assert not learner_thread.is_alive(), "remote training did not finish"
    worker_thread.join(timeout=30)

    assert os.path.exists("models/latest.ckpt")
    assert os.path.exists("models/2.ckpt")
    records = [json.loads(l) for l in open("metrics.jsonl")]
    assert len(records) >= 2
    assert learner.num_returned_episodes >= 22


@pytest.mark.slow
def test_worker_chaos_kill_and_rejoin(tmp_path, monkeypatch):
    """Actor-plane elasticity under real failure: a remote worker process
    is SIGKILLed mid-epoch and a fresh one joins — training keeps
    consuming episodes, finishes every epoch, and shutdown still drains
    (reference claim: workers join/leave freely, worker.py:199-213; drop
    handling connection.py:198-224)."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time

    import yaml

    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    entry_port, data_port = free_port(), free_port()
    cfg = {
        "env_args": {"env": "TicTacToe"},
        "train_args": {
            "batch_size": 8,
            "forward_steps": 4,
            "minimum_episodes": 10,
            "update_episodes": 12,
            "maximum_episodes": 200,
            "epochs": 3,
            "num_batchers": 1,
            "eval_rate": 0.2,
            "mesh": {"dp": 1},  # TCP-transport test, not a sharding test
            "worker": {
                "num_parallel": 2,
                "entry_port": entry_port,
                "data_port": data_port,
            },
        },
        "worker_args": {
            "server_address": "localhost",
            "num_parallel": 2,
            "entry_port": entry_port,
        },
    }
    args = normalize_args(cfg)
    with open("config.yaml", "w") as f:
        yaml.safe_dump(cfg, f)

    learner = Learner(args, remote=True)
    learner_thread = threading.Thread(target=learner.run, daemon=True)
    learner_thread.start()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "PYTHONPATH": repo,
        "JAX_PLATFORMS": "cpu",  # a child must never claim the parent's chip
    }

    def spawn_worker():
        return subprocess.Popen(
            [sys.executable, os.path.join(repo, "main.py"), "--worker"],
            cwd=tmp_path,
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )

    victim = spawn_worker()
    try:
        # let it join and deliver a few episodes, then kill it without warning
        deadline = time.time() + 120
        while learner.num_returned_episodes < 4 and time.time() < deadline:
            time.sleep(0.5)
        assert learner.num_returned_episodes >= 4, "first worker never delivered"
        episodes_before_kill = learner.num_returned_episodes
    finally:
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)

    time.sleep(1.0)  # give the hub a beat to notice the dropped connections
    replacement = spawn_worker()
    try:
        learner_thread.join(timeout=420)
        assert not learner_thread.is_alive(), "training did not survive the worker kill"
        # the replacement actually contributed: episode flow resumed past
        # whatever the victim had delivered before dying
        assert learner.num_returned_episodes > episodes_before_kill
        assert os.path.exists("models/latest.ckpt")
        assert os.path.exists("models/3.ckpt")
        records = [json.loads(l) for l in open("metrics.jsonl")]
        assert len(records) >= 3
    finally:
        replacement.terminate()
        try:
            replacement.wait(timeout=30)
        except subprocess.TimeoutExpired:
            replacement.kill()


# -- network battle mode ----------------------------------------------------


@pytest.mark.slow
def test_network_battle_mode(capsys):
    from handyrl_tpu.runtime.battle import eval_client_main, eval_server_main

    port = free_port()
    args = normalize_args({"env_args": {"env": "TicTacToe"}, "train_args": {}})

    server = threading.Thread(
        target=eval_server_main, args=(args, ["2"]), kwargs={"port": port}, daemon=True
    )
    server.start()

    clients = [
        threading.Thread(
            target=eval_client_main,
            args=(args, [spec, "localhost"]),
            kwargs={"port": port},
            daemon=True,
        )
        for spec in ("random", "random")
    ]
    for c in clients:
        c.start()

    server.join(timeout=120)
    assert not server.is_alive(), "battle server did not finish"
    for c in clients:
        c.join(timeout=30)

    out = capsys.readouterr().out
    assert "total =" in out
    assert "game 0" in out and "game 1" in out
