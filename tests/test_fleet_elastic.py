"""Elastic fleet (docs/serving.md §Elastic fleet).

Pinned smallest-first:

* the pure ``AutoscaleDecider`` hysteresis contract — SLO-breach
  scale-up, never-stack-cold-replicas, cooldown, sustained-calm
  scale-down — socket-free;
* warm-then-admit — a connected replica with no published engine is
  WARMING, not live: it takes zero traffic until its probe passes, and
  a fleet with no warm replica refuses to serve at all;
* the zero-loss retire: seal → drain → migrate the whole SessionCache
  (device residents AND spill-ring entries) to a successor, sessions
  continue BIT-IDENTICAL to an unmigrated control with zero counted
  affinity misses;
* the slow e2es: a load storm scaling the fleet up (no request shed
  into a cold engine) and back down (sessions migrated off the retiring
  replica), and a SIGTERM-preempted subprocess replica handing its
  sessions off inside its drain deadline and exiting 75.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from handyrl_tpu.envs import make_env
from handyrl_tpu.fleet import FleetRouter, ReplicaSpec
from handyrl_tpu.fleet.autoscale import AutoscaleDecider
from handyrl_tpu.models import init_variables
from handyrl_tpu.serving import ModelRouter, ServingClient, ServingServer

pytestmark = pytest.mark.fleet

# tests/ is not a package: the small fleet fixtures are duplicated from
# tests/test_fleet.py rather than imported
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
    if params is not None:
        router.publish(1, params)
    return ServingServer(router, cfg).run()


def _fleet(server_ports, connect_timeout=5.0, **overrides):
    cfg = dict(FLEET_CFG, **overrides)
    cfg["replicas"] = [
        e if isinstance(e, dict) else f"127.0.0.1:{e}" for e in server_ports
    ]
    return FleetRouter(cfg).run(connect_timeout=connect_timeout)


def _wait_for(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out after {timeout}s waiting for {what}")


# ---------------------------------------------------------------------------
# AutoscaleDecider (socket-free hysteresis)
# ---------------------------------------------------------------------------


_DECIDER_CFG = {
    "min_replicas": 1,
    "max_replicas": 3,
    "shed_slo": 0.01,
    "depth_high": 8.0,
    "depth_low": 1.0,
    "scale_down_after_s": 5.0,
    "cooldown_s": 2.0,
}


def test_decider_scales_up_on_slo_breach_with_cooldown():
    d = AutoscaleDecider(_DECIDER_CFG)
    # shed rate over the SLO: up
    assert d.decide(10.0, 1, 0, shed_rate=0.05, depth_mean=0.0) == "up"
    # still breaching inside the cooldown: hold
    assert d.decide(11.0, 2, 0, shed_rate=0.05, depth_mean=0.0) is None
    # cooldown expired but the previous spawn is still warming: never
    # stack cold replicas
    assert d.decide(13.0, 2, 1, shed_rate=0.05, depth_mean=0.0) is None
    # warm now: up again
    assert d.decide(14.0, 2, 0, shed_rate=0.05, depth_mean=0.0) == "up"
    # at max_replicas: hold no matter the load
    assert d.decide(17.0, 3, 0, shed_rate=0.9, depth_mean=99.0) is None


def test_decider_scales_up_on_depth_pressure():
    d = AutoscaleDecider(_DECIDER_CFG)
    # depth crosses before shedding starts — scale on pressure, not pain
    assert d.decide(10.0, 1, 0, shed_rate=0.0, depth_mean=9.0) == "up"


def test_decider_restores_floor_unconditionally():
    d = AutoscaleDecider(_DECIDER_CFG)
    assert d.decide(10.0, 1, 0, shed_rate=0.05, depth_mean=0.0) == "up"
    # below min_replicas (replica lost): restore the floor even inside
    # the cooldown, even with zero load — the floor IS the contract
    assert d.decide(10.5, 0, 0, shed_rate=0.0, depth_mean=0.0) == "up"


def test_decider_scales_down_only_after_sustained_calm():
    d = AutoscaleDecider(_DECIDER_CFG)
    # calm but not yet sustained: hold
    assert d.decide(10.0, 2, 0, shed_rate=0.0, depth_mean=0.0) is None
    assert d.decide(13.0, 2, 0, shed_rate=0.0, depth_mean=0.0) is None
    # a load blip resets the calm clock
    assert d.decide(14.0, 2, 0, shed_rate=0.0, depth_mean=4.0) is None
    assert d.decide(15.0, 2, 0, shed_rate=0.0, depth_mean=0.0) is None
    assert d.decide(18.0, 2, 0, shed_rate=0.0, depth_mean=0.0) is None
    # sustained 5s of calm since the blip: down
    assert d.decide(20.1, 2, 0, shed_rate=0.0, depth_mean=0.0) == "down"
    # never below the floor, no matter how calm
    assert d.decide(30.0, 1, 0, shed_rate=0.0, depth_mean=0.0) is None
    assert d.decide(40.0, 1, 0, shed_rate=0.0, depth_mean=0.0) is None


# ---------------------------------------------------------------------------
# warm-then-admit
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~3.5s of socket warm-probe waits; CI fleet step runs it
def test_spawned_replica_without_device_fails_at_once(monkeypatch):
    """One process per chip: a replica spawned where it cannot get a
    device must say so at once, not wait out spawn_timeout_s (the child
    inherits a platform jax cannot initialize; this process keeps its
    own, already-initialized CPU backend)."""
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.fleet.autoscale import ProcessReplicaFactory

    factory = ProcessReplicaFactory(
        normalize_args({"env_args": {"env": "TicTacToe"}, "train_args": {}}),
        spawn_timeout_s=600.0,
    )
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_platform")
    t0 = time.monotonic()
    with pytest.raises(OSError, match="found no device"):
        factory.spawn()
    assert time.monotonic() - t0 < 60.0
    factory.close()


def test_cold_replica_is_warming_not_live_until_published(tmp_path):
    """A connected replica with NO published engine takes zero traffic:
    it shows as warming, every request lands on the warm replica, and
    publishing flips it to admitted without operator help."""
    module, obs, params = _env_model("TicTacToe")
    warm = _start_server(module, obs, params, tmp_path / "warm")
    cold_cfg = dict(SERVING_CFG)
    cold_router = ModelRouter(module, obs, cold_cfg,
                              model_dir=str(tmp_path / "cold"))
    cold = ServingServer(cold_router, cold_cfg).run()  # nothing published
    fleet = _fleet([warm.bound_port, cold.bound_port], stats_poll_s=0.05)
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        stats = client.stats()
        assert stats["fleet_replicas_live"] == 2
        assert stats["fleet_replicas_warming"] == 1
        # the cold replica's engine serves nothing while it warms
        for _ in range(6):
            assert client.infer(obs) is not None
        cold_rep = next(r for r in fleet._reps()
                        if r.spec.port == cold.bound_port)
        assert not cold_rep.admitted
        assert cold_rep.picked == 0, "a warming replica takes no traffic"
        # publish: the admit probe notices and opens it to traffic
        cold_router.publish(1, params)
        _wait_for(lambda: cold_rep.admitted, 10.0,
                  "cold replica admission after publish")
        assert client.stats()["fleet_replicas_warming"] == 0
    finally:
        client.close()
        fleet.shutdown()
        warm.shutdown()
        cold.shutdown()


def test_fleet_refuses_to_serve_with_no_warm_replica(tmp_path):
    """The startup gate: an all-cold fleet must fail LOUDLY instead of
    binding and shedding the first requests into compile pauses."""
    module, obs, _ = _env_model("TicTacToe")
    cfg = dict(SERVING_CFG)
    router = ModelRouter(module, obs, cfg, model_dir=str(tmp_path))
    cold = ServingServer(router, cfg).run()  # never published
    try:
        with pytest.raises(ConnectionError, match="warm"):
            _fleet([cold.bound_port], connect_timeout=1.5, stats_poll_s=0.05)
    finally:
        cold.shutdown()


# ---------------------------------------------------------------------------
# planned retire: seal -> drain -> migrate -> stop, zero-loss
# ---------------------------------------------------------------------------


@pytest.mark.slow  # ~6s (two Geister engines + lockstep); CI fleet step runs it
def test_planned_retire_migrates_sessions_bit_identical(tmp_path):
    """THE migration acceptance pin: retiring a replica moves its whole
    SessionCache — device residents AND spill-ring entries — to the
    successor; the migrated sessions' next replies are BIT-IDENTICAL to
    unmigrated control sessions with the same history, and the fleet-wide
    affinity-miss count does not move (zero sessions lost)."""
    module, obs, params = _env_model("Geister")
    # session_capacity 1: a replica holding two sessions keeps one
    # device-resident and one in the spill ring — the export must move both
    s1 = _start_server(module, obs, params, tmp_path / "a",
                       session_capacity=1, session_spill=8)
    s2 = _start_server(module, obs, params, tmp_path / "b",
                       session_capacity=1, session_spill=8)
    fleet = _fleet([s1.bound_port, s2.bound_port], stats_poll_s=5.0)
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        # open sessions until the victim owns two (round-robin at equal
        # load spreads them 2/2 over 4 opens)
        sids = [client.open_session() for _ in range(4)]
        by_port = {}
        for sid in sids:
            by_port.setdefault(fleet._affinity[sid].spec.port, []).append(sid)
        assert sorted(len(v) for v in by_port.values()) == [2, 2], by_port
        victim_port = s1.bound_port
        migr_sids, ctrl_sids = by_port[victim_port], by_port[s2.bound_port]

        # identical histories: both replicas hold the same seeded params,
        # so serial batch-1 trajectories are bit-identical across them
        for _ in range(3):
            for sid in sids:
                assert client.infer(obs, sid=sid)["sid"] == sid

        baseline = client.stats()
        miss0 = sum(r["session_affinity_miss"]
                    for r in baseline["replicas"].values())
        victim_rep = next(r for r in fleet._reps()
                          if r.spec.port == victim_port)
        migrated = fleet.retire(victim_rep)
        assert migrated == 2, "both tiers must travel"

        # affinity re-pinned to the survivor; next steps bit-identical
        # with the unmigrated controls (served via session_restored)
        for sid in migr_sids:
            assert fleet._affinity[sid].spec.port == s2.bound_port
        migr_out = [client.infer(obs, sid=sid, timeout=30)["out"]
                    for sid in migr_sids]
        ctrl_out = [client.infer(obs, sid=sid, timeout=30)["out"]
                    for sid in ctrl_sids]
        for a, b in zip(migr_out, ctrl_out):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(np.asarray(a[k]),
                                              np.asarray(b[k]))

        stats = client.stats()
        survivor = stats["replicas"][f"127.0.0.1:{s2.bound_port}"]
        assert survivor["session_migrated_in"] == 2
        assert survivor["session_restored"] >= 2
        miss1 = sum(r["session_affinity_miss"]
                    for r in stats["replicas"].values())
        assert miss1 - miss0 == 0, "a planned retire loses zero sessions"
        assert stats["fleet_migrations"] == 1
        assert stats["fleet_sessions_migrated"] == 2
        assert stats["fleet_migration_ms"] > 0.0
        # the retired replica left the rotation entirely
        assert stats["fleet_replicas"] == 1
        # retire is idempotent: a second call is a no-op
        assert fleet.retire(victim_rep) == 0
    finally:
        client.close()
        fleet.shutdown()
        s1.shutdown()
        s2.shutdown()


def test_retire_without_successor_is_loud_not_wedged(tmp_path):
    """Retiring the LAST stateful replica cannot migrate anywhere: the
    sessions re-open fresh (counted misses on their next touch), the
    retire itself returns 0 and never hangs."""
    module, obs, params = _env_model("Geister")
    s1 = _start_server(module, obs, params, tmp_path / "a")
    fleet = _fleet([s1.bound_port], stats_poll_s=5.0)
    client = ServingClient("127.0.0.1", fleet.bound_port)
    try:
        sid = client.open_session()
        assert client.infer(obs, sid=sid)["sid"] == sid
        rep = fleet._reps()[0]
        t0 = time.monotonic()
        assert fleet.retire(rep) == 0
        assert time.monotonic() - t0 < 10.0, "retire must be bounded"
        assert sid not in fleet._affinity
    finally:
        client.close()
        fleet.shutdown()
        s1.shutdown()


# ---------------------------------------------------------------------------
# load-storm e2e: scale up under pressure (shed-free), back down when calm
# ---------------------------------------------------------------------------


class _InProcFactory:
    """ReplicaFactory over in-process serving servers — the autoscaler's
    spawn/stop seam without process overhead, for the storm e2e."""

    def __init__(self, make_server):
        self._make = make_server
        self._servers = {}
        self.spawned = 0

    def spawn(self):
        server = self._make(self.spawned)
        self.spawned += 1
        spec = ReplicaSpec("127.0.0.1", server.bound_port)
        self._servers[spec.name] = server
        return spec

    def stop(self, spec):
        server = self._servers.pop(spec.name, None)
        if server is not None:
            server.shutdown()

    def close(self):
        servers, self._servers = dict(self._servers), {}
        for server in servers.values():
            server.shutdown()


@pytest.mark.slow
def test_load_storm_scales_up_shed_free_and_back_down(tmp_path):
    """THE elastic acceptance e2e: a request storm drives the autoscaler
    over depth_high -> scale-up; the new replica warms BEFORE admission
    so not one storm request is shed or errored; calm drives scale-down,
    which retires the newest spawned replica THROUGH the migration path
    (its session moves, zero counted losses)."""
    module, obs, params = _env_model("Geister")

    def make_server(n):
        # max_batch 1 keeps queue depth visible under the storm
        return _start_server(module, obs, params, tmp_path / f"r{n}",
                             max_batch=1, max_wait_ms=0.0,
                             warm_buckets=[1])

    factory = _InProcFactory(make_server)
    fleet = FleetRouter(
        {
            "port": 0, "replicas": [], "stats_poll_s": 0.1,
            "replica_stall_s": 10.0, "rejoin_backoff_s": 0.2,
            "rejoin_backoff_max_s": 1.0, "stats_interval": 0.0,
            "autoscale": {
                "enabled": True, "min_replicas": 1, "max_replicas": 2,
                "interval_s": 0.1, "shed_slo": 0.01, "depth_high": 2.0,
                "depth_low": 1.0, "scale_down_after_s": 0.6,
                "cooldown_s": 0.2, "warm_timeout_s": 60.0,
            },
        },
        replica_factory=factory,
    ).run(connect_timeout=60.0)
    client = ServingClient("127.0.0.1", fleet.bound_port)
    stop = threading.Event()
    errors = []
    served = [0]

    def _storm():
        c = ServingClient("127.0.0.1", fleet.bound_port)
        try:
            while not stop.is_set():
                try:
                    c.infer(obs, timeout=30)
                    served[0] += 1
                except Exception as exc:  # any shed/error fails the pin
                    errors.append(repr(exc))
                    return
        finally:
            c.close()

    threads = [threading.Thread(target=_storm, daemon=True)
               for _ in range(12)]
    try:
        assert client.stats()["fleet_replicas_live"] == 1
        for t in threads:
            t.start()
        # the storm must scale the fleet up, and the new replica must be
        # ADMITTED (warm) — not merely spawned
        _wait_for(
            lambda: fleet.scale_ups >= 1 and sum(
                1 for r in fleet._reps() if r.alive and r.admitted) >= 2,
            60.0, "storm scale-up to a second warm replica",
        )
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, f"storm requests must never fail: {errors[:3]}"
        assert served[0] > 0
        stats = client.stats()
        shed = sum(r.get("serve_shed") or 0
                   for r in stats["replicas"].values())
        assert shed == 0, "warm-then-admit means a scale-up sheds nothing"

        # pin a session to the NEWEST spawned replica (the scale-down
        # victim) so the calm-path retire has state to migrate
        victim = [r for r in fleet._reps() if r.spawned][-1]
        sid = None
        for _ in range(8):
            s = client.open_session()
            if fleet._affinity[s] is victim:
                sid = s
                break
        assert sid is not None, "no session landed on the newest replica"
        assert client.infer(obs, sid=sid)["sid"] == sid
        miss0 = sum(r["session_affinity_miss"]
                    for r in client.stats()["replicas"].values())

        # calm: the autoscaler retires the newest spawned replica through
        # the migration path
        _wait_for(lambda: fleet.scale_downs >= 1, 30.0, "calm scale-down")
        _wait_for(lambda: client.stats()["fleet_replicas_live"] == 1, 15.0,
                  "fleet back at the floor")
        assert fleet.sessions_migrated >= 1
        # the migrated session keeps answering, with zero counted losses
        assert client.infer(obs, sid=sid, timeout=30)["sid"] == sid
        miss1 = sum(r["session_affinity_miss"]
                    for r in client.stats()["replicas"].values())
        assert miss1 - miss0 == 0, "scale-down loses zero sessions"
    finally:
        stop.set()
        client.close()
        fleet.shutdown()
        factory.close()


# ---------------------------------------------------------------------------
# preemption e2e: SIGTERM'd subprocess replica drains inside its deadline
# ---------------------------------------------------------------------------


_REPLICA_CHILD = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from handyrl_tpu.config import normalize_args
from handyrl_tpu.serving.server import serve_main

args = normalize_args({
    "env_args": {"env": "Geister"},
    "train_args": {
        "model_dir": sys.argv[1],
        "drain_deadline_seconds": 20.0,
        "serving": {
            "port": 0, "max_models": 3, "shed_policy": "none",
            "max_batch": 8, "max_wait_ms": 1.0, "warm_buckets": [1],
            "watch_interval": 0.0, "stats_interval": 0.0,
            "session_capacity": 64, "session_spill": 256,
        },
    },
})
serve_main(args)
"""


def _spawn_replica_proc(model_dir, fault_after=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("HANDYRL_FAULT_SIGTERM_REPLICA", None)
    if fault_after is not None:
        env["HANDYRL_FAULT_SIGTERM_REPLICA"] = str(fault_after)
    proc = subprocess.Popen(
        [sys.executable, "-c", _REPLICA_CHILD, str(model_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        text=True,
    )
    port = [None]
    lines = []

    def _reader():
        for line in proc.stdout:
            lines.append(line.rstrip())
            if "listening on port" in line and port[0] is None:
                port[0] = int(line.split("listening on port")[1].split()[0])

    threading.Thread(target=_reader, daemon=True).start()
    deadline = time.monotonic() + 120.0
    while port[0] is None and time.monotonic() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                "replica child died before binding:\n" + "\n".join(lines))
        time.sleep(0.05)
    if port[0] is None:
        proc.kill()
        raise AssertionError(
            "replica child never reported its port:\n" + "\n".join(lines))
    return proc, port[0], lines


@pytest.mark.slow
def test_preempted_replica_drains_sessions_and_exits_75(tmp_path):
    """THE preemption acceptance e2e: a replica process SIGTERM'd mid-
    serve (HANDYRL_FAULT_SIGTERM_REPLICA) hands its sessions to a
    survivor inside drain_deadline_seconds and exits 75 (EX_TEMPFAIL);
    the router re-pins affinity and the migrated session's next reply is
    bit-identical to an unmigrated control — zero hangs, zero losses."""
    _, obs_g, _ = _env_model("Geister")
    steps_before_fault = 3
    victim_proc, victim_port, victim_lines = _spawn_replica_proc(
        tmp_path / "victim", fault_after=steps_before_fault)
    surv_proc, surv_port, surv_lines = _spawn_replica_proc(
        tmp_path / "survivor")
    fleet = None
    client = None
    try:
        fleet = _fleet([victim_port, surv_port], connect_timeout=60.0,
                       stats_poll_s=0.3)
        client = ServingClient("127.0.0.1", fleet.bound_port)

        # a session on each replica: one will migrate, one is the control
        sids = [client.open_session() for _ in range(2)]
        owners = {fleet._affinity[s].spec.port: s for s in sids}
        assert set(owners) == {victim_port, surv_port}, \
            "sessions should spread over both replicas"
        migr_sid, ctrl_sid = owners[victim_port], owners[surv_port]

        # identical histories on both (same fresh-init params in both
        # children).  The victim's Nth reply fires its self-SIGTERM.
        for _ in range(steps_before_fault):
            assert client.infer(obs_g, sid=migr_sid, timeout=30)["sid"] \
                == migr_sid
            assert client.infer(obs_g, sid=ctrl_sid, timeout=30)["sid"] \
                == ctrl_sid

        # the preempted child must drain and exit 75 inside its deadline
        t0 = time.monotonic()
        rc = victim_proc.wait(timeout=40.0)
        assert rc == 75, (rc, "\n".join(victim_lines))
        assert time.monotonic() - t0 < 25.0, \
            "drain must respect drain_deadline_seconds"
        _wait_for(lambda: fleet.preempt_drains >= 1, 10.0,
                  "router preemption drain")
        _wait_for(
            lambda: fleet._affinity.get(migr_sid) is not None
            and fleet._affinity[migr_sid].spec.port == surv_port,
            20.0, "affinity re-pinned to the survivor",
        )

        # the migrated session continues bit-identically to the control
        a = client.infer(obs_g, sid=migr_sid, timeout=30)["out"]
        b = client.infer(obs_g, sid=ctrl_sid, timeout=30)["out"]
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))

        stats = client.stats()
        assert stats["fleet_preempt_drains"] == 1
        assert stats["fleet_sessions_migrated"] >= 1
        survivor = stats["replicas"][f"127.0.0.1:{surv_port}"]
        assert survivor["session_migrated_in"] >= 1
        assert survivor["session_affinity_miss"] == 0, \
            "a drained preemption loses zero sessions"
        assert any("exiting 75 for relaunch" in l for l in victim_lines)
    finally:
        if client is not None:
            client.close()
        if fleet is not None:
            fleet.shutdown()
        for proc in (victim_proc, surv_proc):
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)
