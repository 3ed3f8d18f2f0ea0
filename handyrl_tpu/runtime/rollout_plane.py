"""The device-rollout plane: lanes of on-device self-play, and the thread
that keeps them stepping beside a learner's server loop.

* ``vector_env_of`` + ``Lanes``: the streaming rollout program
  (runtime/device_rollout.py ``build_streaming_fn``) over ``games`` lanes of
  one vector env on one mesh.  ``Lanes.stream(key)`` opens a stream of
  dispatches (env state, hidden tree and key carried between them):
  ``step(params, span)`` is one dispatch, ``drain()`` awaits the last.  The
  actor host's loop (runtime/actor_host.py) and the plane both step through it.
* ``RolloutPlane``: a generation-tokened thread (``device-rollout-<gen>``)
  that dispatches the lanes and ingests their records into the device replay
  rings (without ``device_replay``: generates host episodes) and reports what
  it booked to its host's request queue; a watchdog (``plane-watchdog``) that
  restarts a dead, stalled or param-lagged thread up to
  ``plane_max_restarts`` times and then degrades ``plane: split`` to fused,
  loudly; and the cross-plane flows (runtime/plane.py: versioned params onto
  the actor mesh, records back, the gateway remote actor hosts dial).

The plane knows its host by six callables handed to it at construction; a
test passes lambdas.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from collections import deque
from concurrent.futures import TimeoutError as FutureTimeoutError  # plain Exception subclass until py3.11
from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..parallel.mesh import dispatch_serialized, make_mesh
from ..utils.trace import trace_span
from . import faults
from .device_rollout import build_streaming_fn, make_device_rollout

# cumulative in metrics.jsonl, as pipe_batcher_* / sentinel_* are: rare
# events diffed per epoch would mostly print zeros
WATCHDOG_EVENT_KEYS = (
    "plane_watchdog_stalls",
    "plane_watchdog_restarts",
    "plane_watchdog_degraded",
)


# -- the lanes ----------------------------------------------------------------


def vector_env_of(env, train_args: Dict[str, Any], mesh, games: int,
                  streaming: bool):
    """The vector twin ``games`` device lanes of ``env`` step through, or a
    ValueError HERE — at startup, not as a sharding error inside a daemon
    thread.  ``streaming``: the lanes run on a mesh of their own (an actor
    mesh, an actor host), which only the streaming driver's
    record/reset_done/step hooks allow; DeviceReplay and the fused drivers
    say what they need themselves."""
    name = train_args["env"].get("env")
    vector_env = getattr(env, "vector_env", None)
    if vector_env is None:
        raise ValueError(
            f"device_rollout_games set but env {name} exposes no vector_env()"
        )
    venv = vector_env()
    venv_name = getattr(venv, "__name__", type(venv).__name__)
    n_verify = int(train_args.get("autovec_verify_games", 0))
    if n_verify > 0 and getattr(venv, "__autovec__", False):
        # autovec-lifted twin: refuse to generate from a divergent lift
        # (random-game step-parity vs the numpy rules; raises AutovecError
        # naming the diverged observable)
        venv.verify(n_verify, int(train_args["seed"]))
        print(f"autovec twin verified: {venv_name} parity over {n_verify} random games")
    if streaming and not hasattr(venv, "record"):
        raise ValueError(
            "plane: split and distributed.role: actor need a STREAMING vector "
            "env (record/reset_done/step hooks) — the episodic driver runs on "
            f"the default device, not a mesh of its own; {venv_name} lacks them"
        )
    dp = mesh.shape.get("dp", 1)
    if streaming and games % dp:
        raise ValueError(
            f"device_rollout_games {games} not divisible by the {dp} devices "
            "its lanes shard over (actor_chips under plane: split, an actor "
            "host's local devices)"
        )
    if train_args["observation"] and not hasattr(venv, "observe_mask"):
        raise ValueError(
            "device_rollout_games with observation: true requires a "
            "vector env that records observer views (an observe_mask "
            f"hook); {venv_name} records acting players only — use host "
            "actors instead"
        )
    return venv


class Lanes:
    """The streaming rollout program over ``games`` lanes of ``venv``.
    ``mesh`` scopes every dispatch; the program's shardings are pinned to it
    where it has more than one device or ``pin`` says so (an actor mesh of
    one chip), else the program runs where its arguments lie."""

    def __init__(self, venv, module, train_args: Dict[str, Any], mesh,
                 games: int, counters: bool = False, pin: bool = False):
        self.venv, self.module, self.mesh, self.games = venv, module, mesh, games
        self.fn = build_streaming_fn(
            venv, module, games,
            int(train_args["device_replay_k_steps"]),
            mesh=mesh if pin or mesh.size > 1 else None,
            use_observe_mask=bool(train_args["observation"]),
            counters=counters,
        )

    def stream(self, key, commit: bool = False) -> "LaneStream":
        return LaneStream(self, key, commit)


class LaneStream:
    """One stream of dispatches over a ``Lanes``: fresh games from ``key``,
    then ``step`` after ``step``.  A restarted generation opens its own, so a
    superseded thread waking up late steps only what it owns."""

    def __init__(self, lanes: Lanes, key, commit: bool):
        self._fn, self._mesh = lanes.fn, lanes.mesh
        key, k0 = jax.random.split(key)
        vstate = lanes.venv.init(lanes.games, k0)
        hidden = lanes.module.initial_state((lanes.games, lanes.venv.num_players))
        if commit:
            # commit every dispatch input onto the rollout mesh UP FRONT:
            # the args then match the program's pinned in_shardings, so no
            # dispatch triggers an implicit host->mesh reshard.  That copy
            # is not just a transfer on the hot path: under plane: split it
            # races the async ingest on the OTHER plane's devices (seen on
            # the multi-process CPU backend as Execute() placement errors
            # killing the rollout thread).  split() of a committed key runs
            # on the mesh and its outputs inherit the placement
            lane = NamedSharding(lanes.mesh, PartitionSpec("dp"))
            key = jax.device_put(key, NamedSharding(lanes.mesh, PartitionSpec()))
            vstate = jax.device_put(vstate, lane)
            if hidden is not None:
                hidden = jax.device_put(hidden, lane)
        self._key, self._vstate, self._hidden = key, vstate, hidden

    def step(self, params, span) -> Tuple:
        """One dispatch under ``span``: ``(records,)``, or ``(records,
        counted)`` from lanes built with ``counters``."""
        self._key, sub = jax.random.split(self._key)
        with span:
            self._vstate, self._hidden, *out = dispatch_serialized(
                lambda: self._fn(params, self._vstate, self._hidden, sub), self._mesh
            )
        return out

    def drain(self) -> None:
        """Await the in-flight dispatch: exiting the process with an XLA
        execution still running aborts it (StreamingDeviceRollout.drain)."""
        try:
            jax.block_until_ready(self._vstate)
        except Exception:
            pass


# -- the plane ----------------------------------------------------------------


def _warn(what: str) -> None:
    print(f"[handyrl_tpu] plane watchdog: {what}", file=sys.stderr)


class RolloutPlane:
    # how long one wait on the server loop lasts before the thread beats its
    # heart and looks whether it is still wanted
    PATIENCE_S = 5.0

    def __init__(self, env, module, args: Dict[str, Any], games: int,
                 learner_mesh, actor_mesh, rank: int, *,
                 live, budget_met, snapshot, steps, submit, set_publisher):
        """``games`` lanes on this process's devices.  Of its host: ``live()``,
        the run is neither shut down nor draining; ``budget_met()``, the
        epoch's episode budget is met and the chip is the trainer's until
        the boundary; ``snapshot()``, the model server's ``(epoch, params)``;
        ``steps()``, the trainer's step count; ``submit(kind, payload)``, a
        request onto the server loop's queue, answered through the Future it
        returns; ``set_publisher(cache)``, where the trainer publishes
        versioned params from now on (a cache, the gateway, or None)."""
        self.args, self.module, self.games, self._rank = args, module, games, rank
        self._live, self._budget_met = live, budget_met
        self._snapshot, self._steps = snapshot, steps
        self._submit, self._set_publisher = submit, set_publisher
        self.thread = self._watchdog = None
        self._halt = threading.Event()      # stop(): the watchdog's wake-up
        self._gen = 0                       # generation token: stale loops exit
        self._progress_t = time.monotonic()
        self._dispatched = False
        self.events: Dict[str, int] = {k: 0 for k in WATCHDOG_EVENT_KEYS}
        self._fault_wedge = faults.wedge_rollout()
        # 'fused' trains and self-plays time-sliced on one mesh; 'split'
        # keeps the lanes on a disjoint actor mesh (parallel/mesh.py)
        self.topology = "split" if actor_mesh is not None else "fused"
        self._actor_mesh = actor_mesh
        # pod-slice rung 1: under multi-process SPMD the data plane (lanes,
        # rings, record transfer) is PER PROCESS on this host's local
        # learner devices — only the train step is collective, and the
        # local shard it samples enters via TrainContext.put_batch
        self._data_mesh = learner_mesh
        if jax.process_count() > 1:
            self._data_mesh = make_mesh({"dp": -1}, [
                d for d in learner_mesh.devices.flat
                if d.process_index == jax.process_index()
            ])
        self.replay = self.gateway = None   # DeviceReplay; actor hosts' transport
        # under split: versioned params on the actor mesh, the records'
        # way back, PlaneStats and its last epoch's snapshot
        self._param_cache = self._record_xfer = self._stats = None
        self._stats0: Dict[str, float] = {}

        replay_on = bool(args.get("device_replay"))
        self.venv = vector_env_of(
            env, args, self._roll_mesh, games, streaming=actor_mesh is not None
        )
        if replay_on:
            # data stays on device end to end: rollout records -> rings ->
            # sampled batches -> SGD (runtime/device_replay.py, which
            # validates env, net and config here, at startup).  The rings
            # and their donation contract live on the LEARNER data mesh;
            # under split the records cross over to it
            from .device_replay import DeviceReplay

            self.replay = DeviceReplay(
                self.venv, module, args, self._data_mesh, games,
                slots=args["device_replay_slots"],
            )
        self._build_program()
        if actor_mesh is not None:
            from .plane import PlaneParamCache, PlaneStats, RecordTransfer

            if replay_on:
                self._record_xfer = RecordTransfer(self._data_mesh)
            self._param_cache = PlaneParamCache(actor_mesh)
            self._stats = PlaneStats()
            set_publisher(self._param_cache)
        # pod-slice rung 2: the coordinator (alone: actor hosts dial one
        # port) fronts the cross-host plane — record batches from
        # distributed.actor_hosts land in its rings, versioned params go
        # back over DCN (runtime/plane.py)
        dist_args = args.get("distributed") or {}
        if int(dist_args.get("actor_hosts") or 0) > 0 and jax.process_index() == 0:
            if self.replay is None:
                raise ValueError(
                    "distributed.actor_hosts > 0 needs device_replay: "
                    "true on the learner tier — actor-host record "
                    "batches land in the device replay rings "
                    "(docs/performance.md §Pod-slice topology)"
                )
            from .plane import PlaneGateway

            # one publish surface feeds both transports: the gateway
            # delegates to the local actor-mesh cache when plane: split is
            # also active on this host
            self.gateway = PlaneGateway(
                dist_args, on_records=self._ingest_remote, inner=self._param_cache,
            )
            set_publisher(self.gateway)

    @property
    def _roll_mesh(self):
        return self._actor_mesh if self._actor_mesh is not None else self._data_mesh

    def _build_program(self) -> None:
        """The rollout program on the mesh the lanes run on now: the actor
        mesh, or after a degrade (and without one) the data mesh."""
        shared = (self.venv, self.module, self.args)
        self._lanes = self._episodic = None
        if self.replay is not None:
            self._lanes = Lanes(*shared, self._roll_mesh, self.games,
                                pin=self._actor_mesh is not None)
        else:
            self._episodic = make_device_rollout(*shared, self.games, mesh=self._roll_mesh)

    # -- what the host reads for its epoch record -----------------------------

    def books(self) -> Dict[str, Any]:
        """Cumulative keys of the epoch record: the live topology (flips
        split -> fused after a watchdog degradation), the watchdog's events,
        what the rings have booked, and the actor-host tier's health."""
        record: Dict[str, Any] = {}
        if self.replay is not None:     # host ints the rollout thread keeps
            record["device_game_steps"] = self.replay.counters["game_steps"]
            record["device_rollout_dispatches"] = self.replay.counters["ingests"]
        record["plane"] = self.topology
        record.update(self.events)
        if self.gateway is not None:    # live producers, cumulative losses
            record["dist_actor_hosts"] = int(self.gateway.actor_hosts)
            record["dist_actor_host_losses"] = int(self.gateway.actor_host_losses)
        return record

    def epoch_stats(self, dt: float) -> Dict[str, Any]:
        """Per-epoch plane health over the ``dt`` seconds since the last
        call (diffed cumulative counters): realized actor-plane duty, mean
        param staleness at dispatch, and the cross-plane transfer rate
        (records learner-ward + params actor-ward) — the plane_* keys soaks
        watch next to pipe_*."""
        record: Dict[str, Any] = {}
        # local refs: a concurrent watchdog degrade nulls these attributes
        # between the None-check and the reads — the epoch record must not
        # die on the very degrade it is reporting
        stats, cache, xfer, gateway = (
            self._stats, self._param_cache, self._record_xfer, self.gateway
        )
        if gateway is None and (stats is None or cache is None):
            return record
        snap = stats.snapshot() if stats is not None else {}
        # the gateway's byte count already folds in the local cache
        # (``inner``), so it substitutes rather than adds
        snap["xfer_bytes"] = (
            gateway.bytes_transferred if gateway is not None else cache.bytes_transferred
        ) + (xfer.bytes_transferred if xfer else 0)
        prev = self._stats0
        diff = lambda k: snap.get(k, 0.0) - prev.get(k, 0.0)
        if stats is not None:
            record["plane_actor_busy_frac"] = round(diff("actor_busy_s") / dt, 4)
            record["plane_actor_idle_frac"] = round(diff("actor_idle_s") / dt, 4)
        record["plane_xfer_bytes_per_sec"] = round(diff("xfer_bytes") / dt, 1)
        if diff("actor_dispatches"):
            record["plane_param_lag_mean"] = round(
                diff("param_lag_sum") / diff("actor_dispatches"), 2
            )
        self._stats0 = snap
        return record

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        if self.gateway is not None:
            self.gateway.start()
        self._start_thread()
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, daemon=True, name="plane-watchdog"
        )
        self._watchdog.start()

    def stop(self, timeout: float) -> None:
        """The host is no longer live: answer every further actor-host
        request with a clean stop (they exit 0, not as counted losses), and
        wait for the rollout thread and the watchdog.  Tearing down the
        interpreter while a daemon thread is inside an XLA execute aborts
        the process (C++ exception at exit)."""
        self._halt.set()
        if self.gateway is not None:
            self.gateway.begin_stop()
        deadline = time.monotonic() + timeout
        for thread in (self.thread, self._watchdog):
            if thread is not None:
                thread.join(timeout=max(0.0, deadline - time.monotonic()))

    def close(self) -> None:
        if self.gateway is not None:
            self.gateway.stop()

    def _start_thread(self) -> threading.Thread:
        """(Re)start the device-rollout thread under a fresh generation
        token.  A superseded generation exits at its next liveness check (a
        thread wedged inside a dispatch cannot be killed from Python: it is
        abandoned and its generation invalidated)."""
        self._gen += 1
        self._progress_t = time.monotonic()
        # stall detection arms only after this generation's FIRST dispatch:
        # that one pays jit compilation (minutes for a big model), and
        # calling it a stall would burn the restart budget on a healthy
        # warm-up (a thread that DIES compiling is caught as dead)
        self._dispatched = False
        self.thread = threading.Thread(
            target=self._generation, args=(self._gen,), daemon=True,
            name=f"device-rollout-{self._gen}",
        )
        self.thread.start()
        return self.thread

    def _is_live(self, gen: int) -> bool:
        return self._live() and self._gen == gen

    def _beat(self) -> None:
        """Progress heartbeat for the watchdog: every dispatch, backpressure
        sleep, and server patience-wait counts as liveness — only a thread
        that stops doing ALL of those is stalled."""
        self._progress_t = time.monotonic()

    def _maybe_wedge(self, gen: int, dispatches: int) -> bool:
        """HANDYRL_FAULT_WEDGE_ROLLOUT: after N successful dispatches this
        generation stops heartbeating (simulating a wedged XLA execute) but
        politely exits once superseded or shut down.  Returns True when the
        caller should return."""
        w = self._fault_wedge
        if w is None or dispatches < w[0] or (not w[1] and gen != 1):
            return False
        print(
            f"[fault] wedging rollout thread generation {gen} after "
            f"{dispatches} dispatches (HANDYRL_FAULT_WEDGE_ROLLOUT)",
            file=sys.stderr,
        )
        while self._is_live(gen):
            time.sleep(0.05)  # no _beat: the watchdog must notice
        return True

    # -- the watchdog ------------------------------------------------------------

    def _watchdog_loop(self) -> None:
        """Liveness supervision of the rollout thread.  Detects a dead
        thread, a stalled one (no progress beat within plane_stall_timeout),
        or actor params lagging past plane_param_lag_bound; restarts the
        thread up to plane_max_restarts, then degrades split -> fused
        loudly (the shm-batcher degrade pattern)."""
        timeout = float(self.args.get("plane_stall_timeout", 120.0))
        max_restarts = int(self.args.get("plane_max_restarts", 2))
        lag_bound = int(self.args.get("plane_param_lag_bound", 0))
        restarts = 0
        tick = max(0.05, min(1.0, timeout / 4.0))
        while not self._halt.wait(tick):
            if not self._live():
                return
            thread = self.thread
            if thread is None:
                continue
            dead = not thread.is_alive()
            stall_s = time.monotonic() - self._progress_t
            # pre-first-dispatch silence is compile time, not a stall
            stalled = stall_s > timeout and self._dispatched
            cache = self._param_cache
            lagged = (
                lag_bound > 0
                and cache is not None
                and cache.lag(self._steps()) > lag_bound
            )
            if not (dead or stalled or lagged):
                continue
            reason = (
                "thread died"
                if dead
                else f"no progress for {stall_s:.1f}s (> plane_stall_timeout)"
                if stalled
                else f"param lag {cache.lag(self._steps())} > "
                f"plane_param_lag_bound {lag_bound}"
            )
            self.events["plane_watchdog_stalls"] += 1
            _warn(f"rollout plane unhealthy ({reason})")
            if restarts < max_restarts:
                restarts += 1
                self.events["plane_watchdog_restarts"] += 1
                _warn(f"restarting rollout thread ({restarts}/{max_restarts})")
                self._start_thread()
            elif self.topology == "split":
                self._degrade_to_fused()
            else:
                _warn("restart budget exhausted on the fused plane; giving up "
                      "on the rollout thread (host actors keep generating if "
                      "configured)")
                return

    def _degrade_to_fused(self) -> None:
        """Split -> fused degradation: stop the cross-plane param/record
        flows, rebuild the rollout program on the LEARNER mesh, and restart
        the rollout thread there.  Training continues throughout — the
        learner plane never depended on the actor mesh."""
        self._gen += 1  # invalidate any live generation FIRST
        _warn("restart budget exhausted; degrading split -> fused (rollouts "
              "move to the learner mesh; cross-plane param/record flows stop)")
        if self.gateway is not None:
            # the cross-HOST plane outlives a local split->fused degrade:
            # drop only the actor-mesh delegate, keep publishing to the
            # gateway so remote actor hosts still get fresh params
            self.gateway.inner = None
        self._set_publisher(self.gateway)
        self._param_cache = self._record_xfer = self._stats = None
        self._actor_mesh = None
        self.topology = "fused"
        self.events["plane_watchdog_degraded"] = 1
        try:
            self._build_program()
        except Exception:
            traceback.print_exc()
            _warn("learner-mesh rollout rebuild failed (above); device "
                  "generation stops (training continues on already-ingested "
                  "data / host actors)")
            return
        self._start_thread()

    # -- the rollout thread ------------------------------------------------------

    def _generation(self, gen: int) -> None:
        """Device self-play up to each epoch boundary, pausing once its
        episode budget is met so that the chip alternates between rollouts
        and train steps; over once the watchdog supersedes ``gen``."""
        # a restarted generation must not replay the superseded stream;
        # the 1009 * rank fold decorrelates the per-process lane shares
        # (each rank generates DIFFERENT games into its local rings)
        key = jax.random.PRNGKey(
            self.args["seed"] + 0x5EED + 0x1009 * (gen - 1) + 1009 * self._rank
        )
        if self.replay is not None:
            self._replay_loop(key, gen)
        else:
            self._episodic_loop(key, gen)

    def _actor_params(self):
        """(model_id, params) for the next rollout dispatch: under plane:
        split the versioned actor-mesh cache (bumping the realized-lag
        counter), else the model server's epoch snapshot."""
        cache = self._param_cache       # local refs: a concurrent watchdog
        stats = self._stats             # degrade nulls these attributes
        if cache is None:
            return self._snapshot()
        version, params = cache.latest()
        if stats is not None:
            stats.bump(actor_dispatches=1, param_lag_sum=max(0, self._steps() - version))
        return self._snapshot()[0], params

    def _backpressure(self, stats) -> bool:
        """True where the epoch's episode budget is met: yield the chip."""
        if not self._budget_met():
            return False
        with trace_span("rollout.budget_wait"):
            time.sleep(0.02)
        self._beat()  # backpressure idle is healthy
        if stats is not None:
            stats.bump(actor_idle_s=0.02)
        return True

    def _counts(self, stats, model_id: int, game_steps: int) -> Dict[str, Any]:
        """What the server loop books for ingested episodes; ``stats`` are
        host numbers by now (a deferred ingest's fetch, one dispatch old)."""
        return {
            "episodes": int(stats["episodes"]),
            "players": self.venv.num_players,
            "model_id": model_id,
            "game_steps": game_steps,
            "outcome_sum": float(np.sum(stats["outcome_sum"])),
            "outcome_sq_sum": float(stats["outcome_sq_sum"]),
        }

    def _submit_and_wait(self, kind: str, payload, gen: int) -> bool:
        """Hand ``payload`` to the server loop and wait on the SAME future
        with patience: the loop serves no request while it runs an epoch
        boundary (snapshot wait, checkpoint, eval; minutes with a first-epoch
        compile), and giving up on a fixed timeout would silently kill
        on-device generation for the rest of the run.  False = stop the
        rollout loop (superseded, or the host draining with nothing left to
        feed)."""
        with trace_span("rollout.submit"):
            fut = self._submit(kind, payload)
            while not fut.done():
                try:
                    fut.result(timeout=self.PATIENCE_S)
                    self._beat()  # served: the wait was the server's
                except (TimeoutError, FutureTimeoutError):
                    self._beat()  # waiting on a busy server ≠ a stall
                    if not self._is_live(gen):
                        return False
                except Exception:
                    return False
        return True

    def _replay_loop(self, key, gen: int) -> None:
        """Streaming rollout -> device-ring ingest; only scalar counters
        reach the host, reported to the server loop for the books.  Under
        plane: split the dispatch holds only the ACTOR mesh's locks (it
        overlaps the learner's train dispatches) and the record batch
        crosses to the learner mesh before the ingest, which shares the
        learner's locks with training.  Lanes and cross-plane flows are
        resolved at ENTRY: a restart after a degrade picks up the
        learner-mesh plumbing here, and a late-waking superseded thread dies
        at its liveness check, not on a None deref mid-iteration."""
        record_xfer, plane_stats = self._record_xfer, self._stats
        stream = self._lanes.stream(key, commit=True)
        pending_steps = 0   # game steps from batches that finished 0 episodes
        dispatches = 0
        # model epoch per in-flight deferred ingest, aligned with
        # DeviceReplay's stats FIFO: stats come back one dispatch old, and
        # booked under the CURRENT epoch they would misattribute one
        # k_steps block's generation stats at every model publish
        epoch_fifo: deque = deque()
        try:
            while self._is_live(gen):
                if self._backpressure(plane_stats):
                    continue
                if self._maybe_wedge(gen, dispatches):
                    return
                epoch, params = self._actor_params()
                t_busy = time.perf_counter()
                records, = stream.step(params, trace_span("rollout.dispatch", epoch=epoch))
                if record_xfer is not None:
                    records = record_xfer(records)
                # deferred stats: the records go straight into the rings
                # and the scalar fetch for dispatch N happens only after N+1
                # is enqueued, so this thread never synchronizes on an
                # ingest.  The stats returned are ONE DISPATCH OLD (None on
                # the first); the tail is flushed in the finally below
                epoch_fifo.append(epoch)
                with trace_span("rollout.ingest", epoch=epoch):
                    stats = self.replay.ingest_counted(records, defer=True)
                dispatches += 1
                self._dispatched = True  # arms stall detection
                self._beat()
                if plane_stats is not None:
                    plane_stats.bump(actor_busy_s=time.perf_counter() - t_busy)
                if not self._is_live(gen):
                    return
                if stats is None:
                    continue
                stats_epoch = epoch_fifo.popleft()  # the dispatch they're from
                pending_steps += int(stats["game_steps"])
                if int(stats["episodes"]) == 0:
                    continue   # steps stay in pending_steps for the next report
                counts = self._counts(stats, stats_epoch, pending_steps)
                pending_steps = 0
                if not self._submit_and_wait("device_counts", counts, gen):
                    return
        finally:
            # settle the deferred tail so its episodes still reach the
            # books — but only while the run is live (a watchdog restart):
            # submitted at shutdown it could push the host's episode count
            # over the next boundary and conjure an epoch out of the drain
            try:
                left = self.replay.flush_counted()
            except Exception:
                left = None
            if left and self._live() and (int(left["episodes"]) > 0 or pending_steps):
                # under the oldest in-flight dispatch's epoch: a restart
                # racing a model publish must not book the tail under a
                # model that never generated it.  Same patience as the loop
                # body; a superseded thread gives up and blocks no teardown
                self._submit_and_wait("device_counts", self._counts(
                    left,
                    int(epoch_fifo[0]) if epoch_fifo else self._snapshot()[0],
                    pending_steps + int(left["game_steps"]),
                ), gen)
            if self._gen == gen:    # superseded: the new generation owns them
                stream.drain()
                self.replay.drain()

    def _episodic_loop(self, key, gen: int) -> None:
        """Without device replay: whole episodes to the host's store."""
        roll = self._episodic
        roll_mesh = getattr(roll, "mesh", None)
        if roll_mesh is not None:
            # mesh-resident key, same contract as LaneStream's: dispatch
            # args never ride an implicit host->mesh reshard
            key = jax.device_put(key, NamedSharding(roll_mesh, PartitionSpec()))
        dispatches = 0
        try:
            while self._is_live(gen):
                if self._backpressure(self._stats):
                    continue
                if self._maybe_wedge(gen, dispatches):
                    return
                epoch, params = self._actor_params()
                t_busy = time.perf_counter()
                key, sub = jax.random.split(key)
                episodes = roll.generate(params, sub)
                dispatches += 1
                self._dispatched = True  # arms stall detection
                self._beat()
                stats = self._stats
                if stats is not None:
                    stats.bump(actor_busy_s=time.perf_counter() - t_busy)
                for ep in episodes:
                    ep["args"]["model_id"] = {p: epoch for p in ep["players"]}
                if not self._is_live(gen):
                    return
                if not self._submit_and_wait("device_episodes", episodes, gen):
                    return
        finally:
            if hasattr(roll, "drain") and self._gen == gen:
                roll.drain()

    # -- actor hosts' records ------------------------------------------------------

    def _ingest_remote(self, records: Dict[str, Any]) -> None:
        """Plane-gateway ingest (on a gateway serve thread): validate the
        lane width, ingest into this process's rings, and book the counters
        through the request the local rollout thread uses.  ``defer=False``
        on purpose: the deferred-stats FIFO is the local rollout thread's,
        and a second writer interleaving would misattribute both streams'
        stats; one synchronous scalar fetch a record batch is noise next to
        the DCN payload it rode in on."""
        widths = {x.shape[1] for x in jax.tree.leaves(records)}
        if widths != {self.games}:
            raise ValueError(
                f"plane gateway: record batch lane width {sorted(widths)} "
                f"!= this learner's {self.games} per-process lanes "
                "(device_rollout_games / num_processes must match on both "
                "tiers)"
            )
        stats = self.replay.ingest_counted(records, defer=False)
        if int(stats["episodes"]) <= 0 and int(stats["game_steps"]) <= 0:
            return
        # fire-and-forget: the serve thread must keep answering its actor
        # host; the server loop books the counts when it gets there
        self._submit("device_counts", self._counts(
            stats, self._snapshot()[0], int(stats["game_steps"])
        ))
