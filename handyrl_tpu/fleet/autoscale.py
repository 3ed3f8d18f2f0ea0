"""Fleet autoscaler: replica count driven by the shed-rate SLO
(docs/serving.md §Elastic fleet).

The signals are the ones already flowing: ``FleetRouter``'s stats polls
leave each replica's last ``serve_*`` record on its ``_Replica``; the
autoscaler windows those per-tick (shed delta over request delta =
the fleet shed RATE, mean queue depth = pressure before shedding
starts) and turns them into scale decisions with hysteresis:

* UP when the windowed shed rate crosses ``shed_slo`` or mean depth
  per replica crosses ``depth_high`` — but never while a previous
  spawn is still warming (stacking cold replicas is how thundering
  herds are made), and never inside ``cooldown_s`` of the last action;
* DOWN only after the fleet has been calm (zero sheds, mean depth
  under ``depth_low``) for ``scale_down_after_s`` straight — load
  storms are spiky, and a scale-down mid-lull that forces a scale-up
  seconds later pays two migrations for nothing.

A spawned replica is connected immediately but NOT routed to until its
warm probe passes (warm-then-admit, router_tier.py): a scaling-up fleet
never sheds a request into a cold engine's compile pause.  Scale-down
retires through the router's seal → drain → migrate → stop path, so it
loses zero sessions.

``ReplicaFactory`` is the pluggable "where do replicas come from" seam
— anything with ``spawn() -> ReplicaSpec`` / ``stop(spec)`` / ``close()``
serves.  ``ProcessReplicaFactory`` is the built-in: local serving-plane
processes (spawn context — a JAX parent must never fork), the shape
``main.py --fleet`` uses; a cloud deployment would back the
same protocol with its instance API.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from .router_tier import ReplicaSpec

__all__ = ["AutoscaleDecider", "Autoscaler", "ProcessReplicaFactory"]


# defaults mirrored in config.py DEFAULT_TRAIN_ARGS["fleet"]["autoscale"]
# (config validates; this module must also run with a bare dict in tests)
_DEFAULTS: Dict[str, Any] = {
    "enabled": False,
    "min_replicas": 1,
    "max_replicas": 4,
    "interval_s": 1.0,
    "shed_slo": 0.01,
    "depth_high": 64.0,
    "depth_low": 1.0,
    "scale_down_after_s": 30.0,
    "cooldown_s": 10.0,
    "warm_timeout_s": 120.0,
}


def _knob(cfg: Dict[str, Any], key: str):
    return cfg.get(key, _DEFAULTS[key])


class AutoscaleDecider:
    """The pure decision core — windowed signals in, ``"up"`` /
    ``"down"`` / ``None`` out.  No sockets, no threads, no clock of its
    own (``now`` is an argument), so the hysteresis contract pins
    socket-free in tests/test_fleet_elastic.py."""

    def __init__(self, cfg: Dict[str, Any]):
        cfg = dict(cfg or {})
        self.min_replicas = int(_knob(cfg, "min_replicas"))
        self.max_replicas = int(_knob(cfg, "max_replicas"))
        self.shed_slo = float(_knob(cfg, "shed_slo"))
        self.depth_high = float(_knob(cfg, "depth_high"))
        self.depth_low = float(_knob(cfg, "depth_low"))
        self.scale_down_after_s = float(_knob(cfg, "scale_down_after_s"))
        self.cooldown_s = float(_knob(cfg, "cooldown_s"))
        self._last_action_t: Optional[float] = None
        self._calm_since: Optional[float] = None

    def decide(self, now: float, replicas: int, warming: int,
               shed_rate: float, depth_mean: float) -> Optional[str]:
        """One tick: ``replicas`` counts every non-edge replica (warming
        included — it is capacity already paid for), ``warming`` the
        connected-but-not-yet-admitted subset."""
        if replicas < self.min_replicas:
            # below the floor (lost replicas, first tick): restore it
            # regardless of load or cooldown — the floor IS the contract
            self._calm_since = None
            self._last_action_t = now
            return "up"
        in_cooldown = (
            self._last_action_t is not None
            and now - self._last_action_t < self.cooldown_s
        )
        overloaded = shed_rate > self.shed_slo or depth_mean > self.depth_high
        if overloaded:
            self._calm_since = None
            if replicas < self.max_replicas and warming == 0 and not in_cooldown:
                self._last_action_t = now
                return "up"
            return None
        calm = shed_rate <= 0.0 and depth_mean < self.depth_low
        if not calm:
            self._calm_since = None
            return None
        if self._calm_since is None:
            self._calm_since = now
        if (
            replicas > self.min_replicas
            and warming == 0
            and not in_cooldown
            and now - self._calm_since >= self.scale_down_after_s
        ):
            self._last_action_t = now
            self._calm_since = None
            return "down"
        return None


class Autoscaler:
    """The loop thread: windows the router's polled stats into
    (shed_rate, depth_mean), asks the decider, and drives the router's
    scale_up / scale_down.  Owned and started by ``FleetRouter.run``."""

    def __init__(self, router, cfg: Dict[str, Any]):
        self.router = router
        self.cfg = dict(cfg or {})
        self.interval_s = float(_knob(self.cfg, "interval_s"))
        self.decider = AutoscaleDecider(self.cfg)
        # per-replica previous cumulative counters, keyed by spec name —
        # a replica's window survives list churn around it
        self._prev: Dict[str, Dict[str, float]] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def start(self) -> "Autoscaler":
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="fleet-autoscale"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()

    def signals(self):
        """(replicas, warming, shed_rate, depth_mean) over the window
        since the previous call, from the routers' last polled stats."""
        reps = [r for r in self.router._reps() if not r.is_edge]
        live = [r for r in reps if r.alive and not r.sealed]
        warming = sum(1 for r in live if not r.admitted)
        shed_d = 0.0
        req_d = 0.0
        depths: List[float] = []
        seen = set()
        for rep in live:
            if not rep.admitted:
                continue
            stats = dict(rep._last_stats)
            name = rep.spec.name
            seen.add(name)
            prev = self._prev.get(name, {})
            shed_d += max(
                0.0,
                float(stats.get("serve_shed") or 0.0)
                - float(prev.get("serve_shed") or 0.0),
            )
            req_d += max(
                0.0,
                float(stats.get("serve_requests") or 0.0)
                - float(prev.get("serve_requests") or 0.0),
            )
            depths.append(float(stats.get("serve_depth") or 0.0))
            self._prev[name] = stats
        for name in list(self._prev):
            if name not in seen:
                del self._prev[name]
        shed_rate = shed_d / max(1.0, req_d)
        depth_mean = sum(depths) / len(depths) if depths else 0.0
        return len(live), warming, shed_rate, depth_mean

    def _loop(self) -> None:
        while not self._stop.wait(timeout=self.interval_s):
            if self.router.shutdown_flag:
                return
            try:
                self.tick()
            except Exception as exc:
                # the autoscaler must never die silently mid-run: a fleet
                # stuck at the wrong size is an SLO breach, say so
                print(f"fleet: autoscale tick failed: "
                      f"{type(exc).__name__}: {exc}")

    def tick(self) -> Optional[str]:
        replicas, warming, shed_rate, depth_mean = self.signals()
        action = self.decider.decide(
            time.monotonic(), replicas, warming, shed_rate, depth_mean,
        )
        if action == "up":
            self.router.scale_up(
                reason=f" (shed_rate={shed_rate:.3f} depth={depth_mean:.1f})"
            )
        elif action == "down":
            self.router.scale_down(
                reason=f" (calm: depth={depth_mean:.1f})"
            )
        return action


# -- process-backed replica factory ------------------------------------------


def _spawned_replica_main(pipe, args: Dict[str, Any]) -> None:
    """Child entry (spawn context): one serving replica on an ephemeral
    port.  Binds FIRST and reports the port, THEN publishes/warms — the
    honest cold window warm-then-admit exists for: the router connects
    and probes while the engine compiles, and admits only once
    ``serve_models`` goes live.

    One process per chip: a replica claims a device of its own.  Spawned
    on a host whose chip another process holds, it cannot get one — the
    backend then raises here, before the bind, and the factory reports
    the reason at once instead of waiting out its spawn timeout."""
    try:
        import jax

        jax.devices()
    except Exception as exc:
        pipe.send(f"{type(exc).__name__}: {exc}")
        raise
    from ..envs import make_env, prepare_env
    from ..models import init_variables
    from ..runtime.checkpoint import latest_verified_epoch, load_verified_params
    from ..serving.router import ModelRouter
    from ..serving.server import ServingServer

    train = args["train_args"]
    env_args = args["env_args"]
    prepare_env(env_args)
    env = make_env(env_args)
    module = env.net()
    env.reset()
    template_obs = env.observation(env.players()[0])
    model_dir = train.get("model_dir", "models")
    serving_cfg = dict(train.get("serving") or {}, port=0)

    router = ModelRouter(module, template_obs, serving_cfg, model_dir=model_dir)
    server = ServingServer(router, serving_cfg).run()
    pipe.send(server.bound_port)
    newest = latest_verified_epoch(model_dir)
    if newest > 0:
        template = init_variables(module, env)["params"]
        params = load_verified_params(model_dir, newest, template,
                                      pre_verified=True)
        router.publish(newest, params)
    else:
        router.publish(0, init_variables(module, env)["params"])
    try:
        pipe.recv()  # blocks until the factory says stop (or dies)
    except (EOFError, OSError):
        pass
    server.shutdown()


class ProcessReplicaFactory:
    """Spawn-context serving processes on this host — the built-in
    ``ReplicaFactory``.  ``spawn()`` blocks until the child reports its
    bound port (listening, NOT yet warm: admission is the router's
    probe), ``stop(spec)`` asks the child to exit and reaps it."""

    def __init__(self, args: Dict[str, Any], spawn_timeout_s: float = 120.0):
        import multiprocessing as mp

        self._ctx = mp.get_context("spawn")
        self.args = args
        self.spawn_timeout_s = float(spawn_timeout_s)
        self._procs: Dict[str, Any] = {}  # spec name -> (process, pipe)
        self._lock = threading.Lock()

    def spawn(self) -> ReplicaSpec:
        parent, child = self._ctx.Pipe()
        proc = self._ctx.Process(
            target=_spawned_replica_main, args=(child, self.args), daemon=True
        )
        proc.start()
        child.close()
        deadline = time.monotonic() + self.spawn_timeout_s
        while not parent.poll(0.2):
            if not proc.is_alive():
                raise OSError(
                    f"spawned replica died before reporting a port "
                    f"(exit code {proc.exitcode})"
                )
            if time.monotonic() > deadline:
                proc.terminate()
                raise OSError(
                    f"spawned replica reported no port within "
                    f"{self.spawn_timeout_s:.0f}s"
                )
        reply = parent.recv()
        if not isinstance(reply, int):
            # the child could not get a device (one process per chip)
            raise OSError(f"spawned replica found no device: {reply}")
        port = reply
        spec = ReplicaSpec("127.0.0.1", port)
        with self._lock:
            self._procs[spec.name] = (proc, parent)
        return spec

    def stop(self, spec: ReplicaSpec) -> None:
        with self._lock:
            entry = self._procs.pop(spec.name, None)
        if entry is None:
            return
        proc, pipe = entry
        try:
            pipe.send("stop")
        except (BrokenPipeError, OSError):
            pass
        pipe.close()
        proc.join(timeout=10.0)
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout=5.0)

    def close(self) -> None:
        with self._lock:
            procs, self._procs = dict(self._procs), {}
        for name, (proc, pipe) in procs.items():
            try:
                pipe.send("stop")
            except (BrokenPipeError, OSError):
                pass
            pipe.close()
        for name, (proc, _pipe) in procs.items():
            proc.join(timeout=10.0)
            if proc.is_alive():
                proc.terminate()
