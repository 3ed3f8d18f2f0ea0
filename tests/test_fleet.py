"""Fleet serving tier (docs/serving.md §Fleet tier).

Three layers, pinned smallest-first:

* socket-free SessionCache semantics — open → infer×N → LRU evict to
  the spill ring → bit-identical restore; affinity-miss fallback; close
  releases capacity;
* the serving client's liveness/desync satellites — the stall deadline
  failing pending futures loudly, orphaned reply frames counted;
* wire-level integration — server-resident sessions bit-identical with
  the ship-state path (and ≥5× lighter on the wire), the router's
  bounded replica_lost failover with session re-routing, fleet-wide
  swap, and the edge replica's capability fence.
"""

import socket
import threading
import time

import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.fleet import EdgeReplica, FleetRouter, SessionCache
from handyrl_tpu.models import InferenceModel, init_variables
from handyrl_tpu.runtime.connection import (
    FramedConnection,
    accept_socket_connections,
    open_socket_connection,
)
from handyrl_tpu.serving import ModelRouter, ServingClient, ServingError, ServingServer

pytestmark = pytest.mark.fleet

SERVING_CFG = {
    "port": 0,
    "max_models": 3,
    "slo_ms": 2000.0,
    "shed_policy": "none",
    "max_batch": 8,
    "max_wait_ms": 1.0,
    "warm_buckets": [1, 4, 8],
    "queue_bound": 256,
    "recv_timeout": 0.0,
    "watch_interval": 0.0,
    "stats_interval": 0.0,
    "session_capacity": 64,
    "session_spill": 256,
}

FLEET_CFG = {
    "port": 0,
    "stats_poll_s": 0.2,
    "replica_stall_s": 5.0,
    "rejoin_backoff_s": 0.2,
    "rejoin_backoff_max_s": 1.0,
    "stats_interval": 0.0,
}


def _env_model(name):
    env = make_env({"env": name})
    module = env.net()
    env.reset()
    obs = env.observation(env.players()[0])
    params = init_variables(module, env, seed=1)["params"]
    return module, obs, params


def _start_server(module, obs, params, tmp_path, **cfg_overrides):
    cfg = dict(SERVING_CFG, **cfg_overrides)
    router = ModelRouter(module, obs, cfg, model_dir=str(tmp_path))
    router.publish(1, params)
    server = ServingServer(router, cfg).run()
    return server


def _fleet(server_ports, **overrides):
    cfg = dict(FLEET_CFG, **overrides)
    cfg["replicas"] = [
        e if isinstance(e, dict) else f"127.0.0.1:{e}" for e in server_ports
    ]
    return FleetRouter(cfg).run(connect_timeout=5.0)


# ---------------------------------------------------------------------------
# SessionCache (socket-free)
# ---------------------------------------------------------------------------


def _hidden(seed):
    rng = np.random.RandomState(seed)
    return (rng.randn(3, 4).astype(np.float32), rng.randn(2).astype(np.float32))


def test_session_cache_roundtrip_and_lru_restore():
    cache = SessionCache(capacity=2, spill_capacity=8)
    sids = [cache.open() for _ in range(3)]
    assert len(set(sids)) == 3
    states = {sid: _hidden(i) for i, sid in enumerate(sids)}
    for sid, h in states.items():
        cache.store(sid, h)
    # capacity 2: the LRU (first-stored) session spilled to host
    stats = cache.stats()
    assert stats["session_resident"] == 2
    assert stats["session_spilled"] == 1
    assert stats["session_evictions"] == 1
    # touching the spilled session re-pins it BIT-IDENTICAL and counts
    # the restore; something else becomes LRU and spills in its place
    h, status = cache.lookup(sids[0])
    assert status == "restored"
    for got, want in zip(h, states[sids[0]]):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    stats = cache.stats()
    assert stats["session_restored"] == 1
    assert stats["session_resident"] == 2
    # resident lookups stay resident and exact
    h2, status2 = cache.lookup(sids[0])
    assert status2 == "resident"
    for got, want in zip(h2, states[sids[0]]):
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_session_cache_close_releases_capacity():
    cache = SessionCache(capacity=1, spill_capacity=4)
    a, b = cache.open(), cache.open()
    cache.store(a, _hidden(1))
    cache.store(b, _hidden(2))  # evicts a to spill
    assert cache.close(a) is True
    assert cache.close(a) is False  # already gone, from the spill tier
    assert cache.close(b) is True
    stats = cache.stats()
    assert stats["session_resident"] == 0
    assert stats["session_spilled"] == 0
    assert stats["session_closed"] == 2
    # a closed sid looks up as a miss (fresh-state fallback), counted
    h, status = cache.lookup(a)
    assert h is None and status == "miss"
    assert cache.stats()["session_affinity_miss"] == 1


def test_session_cache_spill_overflow_drops_oldest():
    cache = SessionCache(capacity=1, spill_capacity=1)
    sids = [cache.open() for _ in range(3)]
    for i, sid in enumerate(sids):
        cache.store(sid, _hidden(i))
    # resident: sids[2]; spill(cap 1): sids[1]; sids[0] dropped
    stats = cache.stats()
    assert stats["session_resident"] == 1
    assert stats["session_spilled"] == 1
    assert stats["session_spill_drops"] == 1
    h, status = cache.lookup(sids[0])
    assert h is None and status == "miss"
    # the miss is recoverable: the next store re-adopts the sid
    cache.store(sids[0], _hidden(9))
    h, status = cache.lookup(sids[0])
    assert status in ("resident", "restored")
    assert np.array_equal(np.asarray(h[0]), _hidden(9)[0])


def test_session_cache_overflow_miss_reopens_fresh_not_restore():
    """Satellite accounting pin: a spill-overflowed sid re-surfaces as
    exactly ONE counted affinity miss, the re-adopted sid is a fresh open
    (not a restore, not a second miss), and its eventual close counts as
    a real close — the overflow→miss→reopen ledger stays honest."""
    cache = SessionCache(capacity=1, spill_capacity=1)
    sids = [cache.open() for _ in range(3)]
    for i, sid in enumerate(sids):
        cache.store(sid, _hidden(i))
    # sids[0] dropped off the ring: first lookup is THE counted miss
    h, status = cache.lookup(sids[0])
    assert h is None and status == "miss"
    assert cache.stats()["session_affinity_miss"] == 1
    # a pipelined second lookup before the re-adopting store is a FRESH
    # start, not another miss — one loss event, one count
    h, status = cache.lookup(sids[0])
    assert h is None and status == "fresh"
    stats = cache.stats()
    assert stats["session_affinity_miss"] == 1
    assert stats["session_restored"] == 0, "reopen must not count a restore"
    # the re-adopted sid is live again: store lands it, close releases it
    cache.store(sids[0], _hidden(9))
    closed_before = cache.stats()["session_closed"]
    assert cache.close(sids[0])
    assert cache.stats()["session_closed"] == closed_before + 1


def test_session_cache_store_drops_stale_spill_copy():
    """A stateless-override store (wire hidden wins) must pop the sid's
    stale spill-ring copy: the spilled gauge stays honest and the ring
    slot is freed instead of evicting some other session for it."""
    cache = SessionCache(capacity=1, spill_capacity=4)
    a, b = cache.open(), cache.open()
    cache.store(a, _hidden(1))
    cache.store(b, _hidden(2))       # a evicted to the spill ring
    assert cache.stats()["session_spilled"] == 1
    cache.store(a, _hidden(3))       # fresh store: stale spill copy popped
    stats = cache.stats()
    # b is now the spilled one (evicted by a's store); a's old copy gone
    assert stats["session_spilled"] == 1
    h, status = cache.lookup(a)
    assert status == "resident"
    assert np.array_equal(np.asarray(h[0]), _hidden(3)[0])


def test_session_cache_export_adopt_is_zero_loss_and_bit_identical():
    """Migration seam, socket-free: export_all realizes BOTH tiers and
    the fresh set, clears the source (fork guard: stragglers are loud
    misses), and adopt lands everything on the successor — stateful
    sessions restore bit-identical through the counted spill path and
    fresh sids stay fresh, zero counted losses."""
    src = SessionCache(capacity=1, spill_capacity=8)
    dst = SessionCache(capacity=4, spill_capacity=8)
    sids = [src.open() for _ in range(3)]
    states = {sid: _hidden(i) for i, sid in enumerate(sids)}
    for sid, h in states.items():
        src.store(sid, h)            # capacity 1: two of them spilled
    fresh_sid = src.open()           # opened, never stored
    shipped = src.export_all()
    assert set(shipped["sessions"]) == set(sids)
    assert shipped["fresh"] == [fresh_sid]
    assert src.stats()["session_migrated_out"] == 3
    # the source is CLEARED — a straggler infer is a loud miss, not a fork
    assert src.stats()["session_resident"] == 0
    assert src.stats()["session_spilled"] == 0
    _, status = src.lookup(sids[0])
    assert status == "miss"
    # the successor adopts; every stateful session restores bit-identical
    assert dst.adopt(shipped["sessions"], fresh=shipped["fresh"]) == 3
    assert dst.stats()["session_migrated_in"] == 3
    for sid in sids:
        h, status = dst.lookup(sid)
        assert status == "restored", f"{sid}: {status}"
        for got, want in zip(h, states[sid]):
            assert np.array_equal(np.asarray(got), np.asarray(want))
    assert dst.stats()["session_affinity_miss"] == 0
    # the migrated fresh sid starts fresh on the successor — no phantom miss
    h, status = dst.lookup(fresh_sid)
    assert h is None and status == "fresh"
    assert dst.stats()["session_affinity_miss"] == 0


def test_session_cache_adopt_overflow_is_counted_not_wedged():
    """A too-small successor ring overflows EXACTLY like local spills:
    oldest dropped and counted in session_spill_drops, the rest live."""
    src = SessionCache(capacity=8, spill_capacity=8)
    sids = [src.open() for _ in range(4)]
    for i, sid in enumerate(sids):
        src.store(sid, _hidden(i))
    shipped = src.export_all()
    dst = SessionCache(capacity=8, spill_capacity=2)
    dst.adopt(shipped["sessions"], fresh=shipped["fresh"])
    stats = dst.stats()
    assert stats["session_spilled"] == 2
    assert stats["session_spill_drops"] == 2
    assert stats["session_migrated_in"] == 4


# ---------------------------------------------------------------------------
# client satellites: stall deadline + orphaned replies
# ---------------------------------------------------------------------------


def test_client_stall_deadline_fails_pending_loudly():
    """A peer that holds the socket open but stops sending must fail the
    pending futures with a NAMED error within the stall deadline — never
    hang them until per-call timeouts."""
    sock = open_socket_connection(0)
    sock.listen(8)  # backlog up BEFORE the client connects (the accept
    # generator also listens, but its thread may not have started yet)
    port = sock.getsockname()[1]
    accepted = []
    t = threading.Thread(
        target=lambda: accepted.extend(
            c for c in accept_socket_connections(timeout=5.0, sock=sock, maxsize=1) if c
        ),
        daemon=True,
    )
    t.start()
    client = ServingClient("127.0.0.1", port, stall_timeout=0.5)
    try:
        t0 = time.monotonic()
        fut = client.submit(np.zeros(3, np.float32))
        with pytest.raises(ServingError) as err:
            fut.result(timeout=10)
        assert err.value.kind == "stalled"
        assert time.monotonic() - t0 < 5.0  # bounded, not the 10s timeout
    finally:
        client.close()
        sock.close()


def test_client_idle_connection_survives_stall_deadline():
    """The stall deadline only reaps a peer with requests PENDING: an
    idle bursty client keeps its connection."""
    sock = open_socket_connection(0)
    sock.listen(8)  # backlog up BEFORE the client connects (the accept
    # generator also listens, but its thread may not have started yet)
    port = sock.getsockname()[1]
    conns = []
    t = threading.Thread(
        target=lambda: conns.extend(
            c for c in accept_socket_connections(timeout=5.0, sock=sock, maxsize=1) if c
        ),
        daemon=True,
    )
    t.start()
    client = ServingClient("127.0.0.1", port, stall_timeout=0.5)
    # count the stall windows the receiver sits out idle (its loop looks
    # ``conn.recv`` up on every pass)
    idle_windows = threading.Semaphore(0)
    framed_recv = client.conn.recv

    def counting_recv(timeout=None):
        try:
            return framed_recv(timeout=timeout)
        except socket.timeout:
            idle_windows.release()
            raise

    client.conn.recv = counting_recv
    try:
        t.join(timeout=5)
        assert conns, "server never saw the connection"
        server_conn = conns[0]
        for _ in range(2):  # several idle stall windows pass
            assert idle_windows.acquire(timeout=10), "the receiver never timed out idle"
        # the connection still works.  Request first, then the reply: a
        # reply sent ahead of its request is an orphan if the receiver
        # reads it before submit() has registered the rid (the race that
        # failed this test under load); and a window has just begun, so
        # the request is not pending across a deadline
        fut = client.submit(np.zeros(3, np.float32))
        _, request = server_conn.recv(timeout=5.0)
        server_conn.send(("result", {"rid": request["rid"], "model": 0, "out": {"x": 1}}))
        assert fut.result(timeout=10)["out"] == {"x": 1}
        assert client.replies_orphaned == 0
    finally:
        client.close()
        sock.close()


def test_client_counts_orphaned_replies():
    """Reply frames with a missing/unknown rid (a desynced server) are
    counted, not silently discarded."""
    sock = open_socket_connection(0)
    sock.listen(8)  # backlog up BEFORE the client connects (the accept
    # generator also listens, but its thread may not have started yet)
    port = sock.getsockname()[1]
    conns = []
    t = threading.Thread(
        target=lambda: conns.extend(
            c for c in accept_socket_connections(timeout=5.0, sock=sock, maxsize=1) if c
        ),
        daemon=True,
    )
    t.start()
    client = ServingClient("127.0.0.1", port)
    try:
        t.join(timeout=5)
        assert conns
        conns[0].send(("result", {"rid": 999, "out": {}}))   # unknown rid
        conns[0].send(("result", {"out": {}}))               # missing rid
        deadline = time.monotonic() + 5.0
        while client.replies_orphaned < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert client.replies_orphaned == 2
    finally:
        client.close()
        sock.close()


# ---------------------------------------------------------------------------
# server-resident sessions over the wire (recurrent model)
# ---------------------------------------------------------------------------


def test_sessions_bit_identical_with_ship_state_and_lighter(tmp_path):
    """THE session acceptance pin: a server-resident session replays the
    exact trajectory of the ship-state-both-ways loop — bit-identical
    outputs — while the wire carries no hidden state in either
    direction."""
    module, obs, params = _env_model("Geister")
    server = _start_server(module, obs, params, tmp_path)
    client = ServingClient("127.0.0.1", server.bound_port)
    try:
        steps = 4
        # leg 1: stateless ship-state loop (serial, batch-1: deterministic)
        hidden = InferenceModel(module, {"params": params}).init_hidden()
        shipped = []
        for _ in range(steps):
            out = client.infer(obs, hidden=hidden)["out"]
            hidden = out.pop("hidden")
            shipped.append(out)
        ship_sent, ship_recv = client.wire_bytes()

        # leg 2: the same trajectory through a server-resident session
        sid = client.open_session()
        b0_sent, b0_recv = client.wire_bytes()
        sessioned = []
        for _ in range(steps):
            reply = client.infer(obs, sid=sid)
            assert reply["sid"] == sid
            assert "hidden" not in reply["out"], "session reply shed its state"
            sessioned.append(reply["out"])
        s_sent = client.wire_bytes()[0] - b0_sent
        s_recv = client.wire_bytes()[1] - b0_recv

        for a, b in zip(shipped, sessioned):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        # Geister's DRC hidden (~27 KB/step each way) dwarfs the obs: the
        # session leg must be >= 5x lighter per request in BOTH directions
        assert ship_sent / max(s_sent, 1) >= 5.0
        assert ship_recv / max(s_recv, 1) >= 5.0

        stats = client.stats()
        assert stats["session_opened"] == 1
        assert stats["session_resident"] == 1
        assert client.close_session(sid)["existed"] is True
        assert client.stats()["session_resident"] == 0
    finally:
        client.close()
        server.shutdown()


def test_session_disabled_is_a_loud_bad_request(tmp_path):
    module, obs, params = _env_model("TicTacToe")
    server = _start_server(module, obs, params, tmp_path, session_capacity=0)
    client = ServingClient("127.0.0.1", server.bound_port)
    try:
        with pytest.raises(ServingError) as err:
            client.open_session()
        assert err.value.kind == "bad_request"
        with pytest.raises(ServingError) as err:
            client.infer(obs, sid="s-nope")
        assert err.value.kind == "bad_request"
        # the stateless path is untouched
        assert client.infer(obs)["model"] == 1
    finally:
        client.close()
        server.shutdown()


# ---------------------------------------------------------------------------
# fleet router
# ---------------------------------------------------------------------------


def test_router_proxies_and_balances(tmp_path):
    module, obs, params = _env_model("TicTacToe")
    s1 = _start_server(module, obs, params, tmp_path / "a")
    s2 = _start_server(module, obs, params, tmp_path / "b")
    fleet = _fleet([s1.bound_port, s2.bound_port])
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        direct = InferenceModel(module, {"params": params}).inference(obs)
        futs = [client.submit(obs) for _ in range(32)]
        for fut in futs:
            out = fut.result(timeout=30)
            assert out["model"] == 1
            np.testing.assert_allclose(
                out["out"]["policy"], direct["policy"], rtol=2e-4, atol=2e-5
            )
        stats = client.stats()
        assert stats["fleet_replies"] == 32
        assert stats["fleet_replicas_live"] == 2
        assert len(stats["replicas"]) == 2
        # both replicas actually served (round-robin at equal load)
        assert all(
            r["serve_replies"] >= 1 for r in stats["replicas"].values()
        )
    finally:
        client.close()
        fleet.shutdown()
        s1.shutdown()
        s2.shutdown()


def test_router_failover_is_bounded_and_survivors_serve(tmp_path):
    """THE failover acceptance pin, updated for the elastic fleet's
    bounded-retry contract: an in-flight STATEFUL request on a killed
    replica fails loudly (replica_lost, bounded, never a hang) because a
    session infer is not idempotent from the router's seat — while
    stateless traffic keeps succeeding on the survivor, and the dead
    replica's sessions re-route with a counted affinity miss."""
    module, obs, params = _env_model("Geister")
    s1 = _start_server(module, obs, params, tmp_path / "a")
    s2 = _start_server(module, obs, params, tmp_path / "b")
    # stats_poll 5s: the background poll can't race this test's kill —
    # the first post-kill request is what discovers the dead replica
    fleet = _fleet([s1.bound_port, s2.bound_port], replica_stall_s=2.0,
                   stats_poll_s=5.0)
    client = ServingClient("127.0.0.1", fleet.bound_port)
    servers = {s1.bound_port: s1, s2.bound_port: s2}
    try:
        # two sessions: with round-robin-at-equal-load picks they land on
        # different replicas, so one of them lives on the victim
        sids = [client.open_session() for _ in range(2)]
        for sid in sids:
            assert client.infer(obs, sid=sid)["sid"] == sid
        owners = {rep.spec.port: sid for sid, rep in
                  ((s, fleet._affinity[s]) for s in sids)}
        assert len(owners) == 2, "sessions should spread over both replicas"

        victim_port = s1.bound_port
        servers[victim_port].shutdown()

        # stateful request pinned to the (still-assumed-live) victim:
        # loud bounded replica_lost — never retried, never a hang
        lost_sid = owners[victim_port]
        t0 = time.monotonic()
        with pytest.raises(ServingError) as err:
            client.infer(obs, sid=lost_sid, timeout=15)
        assert err.value.kind == "replica_lost"
        assert time.monotonic() - t0 < 10.0, "failover must be bounded"

        # the survivor keeps serving stateless traffic, no errors
        for _ in range(4):
            assert client.infer(obs, timeout=15) is not None

        # the victim's session re-routes to the survivor: served fresh-
        # state (affinity miss counted there), same sid, no hang
        reply = client.infer(obs, sid=lost_sid, timeout=30)
        assert reply["sid"] == lost_sid
        stats = client.stats()
        assert stats["fleet_replicas_live"] == 1
        assert stats["fleet_replica_lost"] >= 1
        survivor = stats["replicas"][f"127.0.0.1:{s2.bound_port}"]
        assert survivor["session_affinity_miss"] >= 1
    finally:
        client.close()
        fleet.shutdown()
        s1.shutdown()
        s2.shutdown()


@pytest.mark.slow  # ~5s of loss-detection waits; CI fleet step runs it
def test_router_retries_stateless_requests_once_on_replica_loss(tmp_path):
    """Satellite pin, the other half of the failover contract: a no-sid
    in-flight request caught on a dying replica is retried ONCE on a
    survivor (counted in fleet_failover_retries) and succeeds — the
    caller never sees the loss."""
    module, obs, params = _env_model("TicTacToe")
    s1 = _start_server(module, obs, params, tmp_path / "a")
    s2 = _start_server(module, obs, params, tmp_path / "b")
    fleet = _fleet([s1.bound_port, s2.bound_port], replica_stall_s=2.0,
                   stats_poll_s=5.0)
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        assert client.infer(obs) is not None  # fleet warm end-to-end
        # force the next pick onto the victim: the survivor looks loaded
        victim = next(r for r in fleet._reps()
                      if r.spec.port == s1.bound_port)
        for rep in fleet._reps():
            rep.load = 0.0 if rep is victim else 999.0
            rep.picked = 0
        s1.shutdown()
        # routed to the "live" victim, transport fails, retried on the
        # survivor — the caller just sees a reply
        reply = client.infer(obs, timeout=15)
        assert reply is not None
        stats = client.stats()
        assert stats["fleet_failover_retries"] == 1
        assert stats["fleet_replicas_live"] == 1
    finally:
        client.close()
        fleet.shutdown()
        s1.shutdown()
        s2.shutdown()


def test_fleet_wide_swap_flips_every_replica(tmp_path):
    module, obs, params = _env_model("TicTacToe")
    env = make_env({"env": "TicTacToe"})
    params2 = init_variables(module, env, seed=2)["params"]
    s1 = _start_server(module, obs, params, tmp_path / "a")
    s2 = _start_server(module, obs, params, tmp_path / "b")
    fleet = _fleet([s1.bound_port, s2.bound_port])
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        reply = client.swap(2, params=params2)
        assert reply["replicas"] == 2
        assert reply["warm_ms"] >= 0
        # every subsequent request, whichever replica it lands on, serves
        # the new latest
        for _ in range(8):
            assert client.infer(obs)["model"] == 2
        assert client.stats()["fleet_hot_swaps"] == 1
    finally:
        client.close()
        fleet.shutdown()
        s1.shutdown()
        s2.shutdown()


# ---------------------------------------------------------------------------
# edge replica
# ---------------------------------------------------------------------------


def test_edge_replica_serves_wire_protocol():
    module, obs, params = _env_model("TicTacToe")
    model = InferenceModel(module, {"params": params})
    edge = EdgeReplica(model, port=0, workers=2).run()
    client = ServingClient("127.0.0.1", edge.bound_port)
    try:
        direct = model.inference(obs)
        reply = client.infer(obs)
        assert reply["model"] == 0  # one frozen artifact, no generations
        np.testing.assert_allclose(
            reply["out"]["policy"], direct["policy"], rtol=2e-4, atol=2e-5
        )
        stats = client.stats()
        assert stats["serve_replies"] == 1
        # stateful requests are refused loudly, swap likewise
        with pytest.raises(ServingError) as err:
            client.infer(obs, sid="s-x")
        assert err.value.kind == "bad_request"
        with pytest.raises(ServingError) as err:
            client.swap(2, params=params)
        assert err.value.kind == "bad_request"
    finally:
        client.close()
        edge.shutdown()


def test_router_keeps_stateful_routes_off_edge(tmp_path):
    """The capability fence: with an edge replica registered, sessions and
    wire-hidden requests land only on full replicas; stateless requests
    may use edge capacity."""
    module, obs, params = _env_model("Geister")
    full = _start_server(module, obs, params, tmp_path)
    model = InferenceModel(module, {"params": params})
    edge = EdgeReplica(model, port=0, workers=2).run()
    fleet = _fleet([
        full.bound_port,
        {"host": "127.0.0.1", "port": edge.bound_port, "tags": ["edge"]},
    ])
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        sid = client.open_session()
        owner = fleet._affinity[sid]
        assert not owner.is_edge
        for _ in range(3):
            assert client.infer(obs, sid=sid)["sid"] == sid
        # ship-state is stateful too: never routed to edge (which would
        # refuse it) — every request succeeds
        hidden = model.init_hidden()
        out = client.infer(obs, hidden=hidden)["out"]
        assert "hidden" in out
    finally:
        client.close()
        fleet.shutdown()
        edge.shutdown()
        full.shutdown()


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def _cfg(**over):
    train = {"fleet": over.pop("fleet", {}), "serving": over.pop("serving", {})}
    return {"env_args": {"env": "TicTacToe"}, "train_args": train}


def test_fleet_config_validation():
    ok = normalize_args(_cfg())["train_args"]
    assert ok["fleet"]["port"] == 9996
    assert ok["serving"]["session_capacity"] == 1024
    with pytest.raises(ValueError, match="host:port"):
        normalize_args(_cfg(fleet={"replicas": ["nocolon"]}))
    with pytest.raises(ValueError, match="host.*port"):
        normalize_args(_cfg(fleet={"replicas": [{"port": 1}]}))
    with pytest.raises(ValueError, match="stats_poll_s"):
        normalize_args(_cfg(fleet={"stats_poll_s": 0}))
    with pytest.raises(ValueError, match="replica_stall_s"):
        normalize_args(_cfg(fleet={"replica_stall_s": -1}))
    with pytest.raises(ValueError, match="rejoin_backoff_max_s"):
        normalize_args(_cfg(fleet={"rejoin_backoff_s": 5.0,
                                   "rejoin_backoff_max_s": 1.0}))
    with pytest.raises(ValueError, match="edge_workers"):
        normalize_args(_cfg(fleet={"edge_workers": 0}))
    with pytest.raises(ValueError, match="session_capacity"):
        normalize_args(_cfg(serving={"session_capacity": -1}))
    with pytest.raises(ValueError, match="fleet.port"):
        normalize_args(_cfg(fleet={"port": 70000}))
