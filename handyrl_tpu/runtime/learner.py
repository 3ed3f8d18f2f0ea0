"""Central learner: role assignment, episode ingestion, epoch cadence.

Semantics parity with reference Learner (handyrl/train.py:404-633):

* role assignment 'g'/'e' with effective eval rate
  ``max(eval_rate, update_episodes**-0.15)`` (train.py:415-416, 564-576);
* per-model-id generation stats and per-opponent evaluation aggregation
  (train.py:457-500);
* epoch boundary every ``update_episodes`` returned episodes after a
  ``minimum_episodes`` warmup; trainer handoff; epoch-indexed checkpoints
  (train.py:540-626);
* shutdown after ``epochs`` epochs; 'args' answered None so workers drain.

TPU-first differences: workers are in-process threads sharing the batched
inference engine (runtime/worker.py), requests arrive on a queue consumed
by this single server loop (the reference's QueueCommunicator collapses to
queue.Queue — no sockets locally), and each epoch appends a machine-
readable metrics record (metrics.jsonl) alongside the human log lines the
reference's plotters parse (win_rate_plot.py:34-45).
"""

from __future__ import annotations

import json
import os
import queue
import random
import signal
import sys
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

from ..envs import make_env, prepare_env
from ..models import init_variables
from ..parallel import is_coordinator, make_mesh
from ..utils import trace
from ..utils.trace import trace_phase, trace_span
from . import faults
from .checkpoint import (
    gc_snapshots,
    latest_verified_epoch,
    load_verified_params,
    save_epoch_snapshot,
    verify_state,
)
from .rollout_plane import RolloutPlane
from .trainer import Trainer
from .worker import LocalModelServer, LocalWorkerPool

# Exit status after a preemption-safe drain (SIGTERM/SIGINT): the run
# stopped with a VERIFIED resume point on disk and wants to be relaunched
# with ``restart_epoch: -1``.  75 = BSD EX_TEMPFAIL ("temporary failure,
# retry"), the conventional please-reschedule-me code supervisors honor.
EXIT_RESUMABLE = 75


class Learner:
    def __init__(self, args: Dict[str, Any], net=None, remote: bool = False):
        # once a learner: the planes, the rings, the engines, the restore
        with trace_phase("setup.learner", plane="learner"):
            self._build(args, net, remote)

    def _build(self, args: Dict[str, Any], net, remote: bool):
        train_args = dict(args["train_args"])
        train_args["env"] = args["env_args"]
        self.args = train_args

        # -- multi-process role (parallel/distributed.py) -----------------
        # jax.distributed must already be initialized by the entry point
        # (main.py calls init_distributed before constructing the Learner);
        # single-process runs see nprocs == 1 and none of the distributed
        # machinery below activates.
        import jax

        from ..parallel.distributed import process_index

        self._dist_nprocs = jax.process_count()
        self._dist_rank = process_index() if self._dist_nprocs > 1 else 0
        self._dist_follower = self._dist_nprocs > 1 and not is_coordinator()
        # generation diversity: each process contributes DIFFERENT episodes
        # to the global batch (the model-init seed stays the base seed on
        # every process — params must start identical everywhere)
        random.seed(self.args["seed"] + 1009 * self._dist_rank)
        # host-loss fault injections (runtime/faults.py), parsed here so
        # tests set the env right before construction; malformed = loud
        self._fault_kill_proc = faults.kill_process_at_epoch()
        self._fault_wedge_proc = faults.wedge_process_at_epoch()
        self._health = None
        self._collective_watchdog = None
        self._host_faulted = False
        # -- observability plane (docs/observability.md) ------------------
        # span tracing arms here, BEFORE any pipeline/trainer construction,
        # so startup dispatches are in the trace too; configure() validates
        # the sink is writable (a run asked to trace must fail loudly at
        # startup).  Off by default: trace_span is then one attribute check
        if trace.configure(self.args.get("trace"), rank=self._dist_rank):
            print(f"trace: spans -> {trace.current_path()} (rank {self._dist_rank})")
        self._rank_metrics = bool(
            (self.args.get("observability") or {}).get("rank_metrics", True)
        )

        prepare_env(args["env_args"])
        self.env = make_env(args["env_args"])
        eval_modify_rate = (self.args["update_episodes"] ** 0.85) / self.args["update_episodes"]
        self.eval_rate = max(self.args["eval_rate"], eval_modify_rate)
        self.shutdown_flag = False

        self.model_dir = self.args.get("model_dir", "models")
        self.module = net if net is not None else self.env.net()
        variables = init_variables(self.module, self.env, self.args["seed"])
        params = variables["params"]

        self.model_epoch = self.args["restart_epoch"]
        auto_resumed = False
        if self.model_epoch < 0:
            # auto-resume: newest manifest entry whose snapshot digest
            # still verifies, falling back to older verified epochs when a
            # crash or bit-rot corrupted the newest one (0 = fresh start)
            if self._dist_nprocs > 1:
                # every SPMD process must resume the SAME epoch, and only
                # the coordinator writes checkpoints — so only IT scans
                # (the digest sweep can stream many GB; N-1 redundant
                # sweeps of a shared filesystem would all be discarded)
                # and broadcasts its verdict (parallel/distributed.py,
                # pinned by the 2-process resume test).  On a NON-shared
                # model_dir the other processes then fail LOUDLY below
                # (load_verified_params can't find the file) instead of
                # silently feeding fresh seed params into the collective
                # train step, exactly like an explicit restart_epoch.
                from ..parallel.distributed import broadcast_resume_epoch

                local = latest_verified_epoch(self.model_dir) if is_coordinator() else 0
                self.model_epoch = broadcast_resume_epoch(local)
                # coordinator-verified, not locally verified, off process 0
                auto_resumed = self.model_epoch > 0 and is_coordinator()
            else:
                self.model_epoch = latest_verified_epoch(self.model_dir)
                auto_resumed = self.model_epoch > 0
            print(
                f"auto-resume (restart_epoch: -1): epoch {self.model_epoch}"
                if self.model_epoch > 0
                else "auto-resume (restart_epoch: -1): no verified snapshot; fresh start"
            )
        if self.model_epoch > 0:
            # refuses a digest-mismatched file: silently training on a
            # corrupt snapshot is the one unrecoverable failure mode
            # (pre_verified: auto-resume just digest-scanned this epoch)
            params = load_verified_params(
                self.model_dir, self.model_epoch, params, pre_verified=auto_resumed
            )

        # generated datum
        self.generation_results: Dict[int, tuple] = {}
        self.num_episodes = 0
        self.num_returned_episodes = 0

        # evaluated datum
        self.results: Dict[int, tuple] = {}
        self.results_per_opponent: Dict[int, Dict[str, tuple]] = {}
        self.num_results = 0

        # device-plane topology: 'fused' trains and self-plays time-sliced
        # on one mesh; 'split' carves disjoint learner/actor meshes so both
        # planes dispatch concurrently (per-device locks, parallel/mesh.py)
        self._actor_mesh = None
        if self.args.get("plane", "fused") == "split":
            from ..parallel import split_mesh

            mesh, self._actor_mesh = split_mesh(
                self.args.get("mesh"), int(self.args["actor_chips"])
            )
            print(
                "device planes: split — learner %s on devices %s, actor "
                "{'dp': %d} on devices %s (param refresh every %d updates)"
                % (
                    dict(mesh.shape),
                    [d.id for d in mesh.devices.flat],
                    self._actor_mesh.size,
                    [d.id for d in self._actor_mesh.devices.flat],
                    int(self.args["param_refresh_updates"]),
                )
            )
        else:
            mesh = make_mesh(self.args.get("mesh"))
        if self.args.get("obs_int8"):
            # thread the generator's quantization spec to the train step:
            # forward_prediction dequantizes int8 obs planes under
            # args['_obs_quant'], derived once from the same env metadata
            # generation.py quantizes with
            from ..models.quantize import obs_quant_spec

            self.env.reset()
            self.args["_obs_quant"] = obs_quant_spec(
                self.env, obs=self.env.observation(self.env.players()[0])
            )
        self.trainer = Trainer(self.args, self.module, params, mesh)
        if self._dist_nprocs > 1:
            # distributed epoch loop: the coordinator's boundary/shutdown/
            # drain decisions reach every trainer as tiny broadcast
            # collectives (parallel/distributed.py), and the health plane
            # + collective watchdog bound a lost or wedged peer
            # (parallel/health.py — started in run())
            from ..parallel.distributed import DistributedCadence
            from ..parallel.health import CollectiveWatchdog, HostHealthPlane

            dist_args = dict(self.args.get("distributed") or {})
            self.trainer.cadence = DistributedCadence(self.trainer.ctx.mesh)
            timeout = float(dist_args.get("collective_timeout") or 0.0)
            if timeout > 0:
                self._collective_watchdog = CollectiveWatchdog(
                    timeout,
                    lambda reason: self._host_fault(reason, "collective_timeout"),
                )
                self.trainer.collective_watchdog = self._collective_watchdog
            if dist_args.get("coordinator_address"):
                self._health = HostHealthPlane(
                    dist_args,
                    self._dist_rank,
                    self._dist_nprocs,
                    lambda reason, kind: self._host_fault(reason, kind),
                )
            # the agreed stop/drain boundary reaches every rank in the same
            # broadcast; from there peer silence is teardown, not a fault —
            # run() teardown is too late (ranks skew by worker joins /
            # final fetches, and the skewed rank would exit 75 out of a
            # clean run)
            self.trainer.on_agreed_finish = self._disarm_host_fault
            print(
                "distributed learner: process %d/%d (%s), health plane %s, "
                "collective watchdog %s"
                % (
                    self._dist_rank,
                    self._dist_nprocs,
                    "coordinator" if not self._dist_follower else "follower",
                    "on" if (self._health and self._health.enabled) else "off",
                    f"{timeout:.0f}s" if timeout > 0 else "off",
                )
            )
        # the CONFIGURED assembly plane (start() hasn't run yet, so an shm
        # pipeline could still fall back to threads); metrics records read
        # the live mode from batcher.stats() at each epoch, which is the
        # attributable value — this line is the intent, not the outcome
        self.batch_pipeline_mode = getattr(self.trainer.batcher, "mode", "thread")
        print(
            "batch pipeline: %s configured (num_batchers=%d)"
            % (self.batch_pipeline_mode, self.args["num_batchers"])
        )
        if self.model_epoch > 0:
            state_path = os.path.join(self.model_dir, "state.ckpt")
            if not os.path.exists(state_path):
                print(f"{state_path} not found; resuming with a fresh optimizer")
            elif verify_state(self.model_dir, self.model_epoch) is False:
                # recorded digest mismatch: truncated/corrupt optimizer
                # state — params are verified above, so branch with a
                # fresh optimizer instead of deserializing garbage
                print(
                    f"{state_path} fails digest verification; "
                    "resuming with a fresh optimizer"
                )
            else:
                # adopts Adam moments + step count + lr EMA, but only when
                # the file matches restart_epoch (an earlier epoch = branch)
                self.trainer.load_state(state_path, self.model_epoch)
        self.model_server = self._make_model_server(args)
        router = getattr(self.model_server, "_router", None)
        if router is not None and getattr(router, "weight_dtype", "") == "int8":
            # publish-time int8 calibration replays REAL stored episodes:
            # the learner owns the episode store the router samples from
            from ..models.quantize import calibration_batches_from_store

            _store = self.trainer.store
            router.calibration_source = lambda: calibration_batches_from_store(
                _store, router.calibration_batches
            )
        self.model_server.publish(self.model_epoch, params)

        self.remote = remote
        if remote:
            from .server import WorkerServer  # noqa: avoid socket deps locally

            self.worker = WorkerServer(self.args, self.handle, self.model_server)
        else:
            self.worker = LocalWorkerPool(self.args, self.handle, self.model_server)

        # -- data flywheel (handyrl_tpu/flywheel/) -------------------------
        # learner side: the harvest ingest thread (started in run()) and
        # the quality-plane rollback signal.  The seq baseline is read at
        # startup so a stale FLYWHEEL_ROLLBACK.json from a previous run is
        # never re-applied — only signals written AFTER this process came
        # up count.
        self._flywheel_cfg = dict(self.args.get("flywheel") or {})
        self._flywheel_ingestor = None
        self.flywheel_rollbacks = 0
        self._flywheel_rollback_seq = 0
        if self._flywheel_cfg.get("enabled"):
            from ..flywheel import read_rollback_signal

            sig = read_rollback_signal(self.model_dir)
            self._flywheel_rollback_seq = int(sig.get("seq", 0)) if sig else 0
        # HANDYRL_FAULT_POISON_SNAPSHOT_AT_EPOCH: sabotage one SAVED
        # snapshot (update_model) while training continues on clean params
        self._fault_poison_epoch = faults.poison_snapshot_epoch()

        self._requests: queue.Queue = queue.Queue()
        self._active_workers = 0
        self._shutdown_t0 = 0.0
        self._epoch_t0 = time.time()
        self._epoch_steps0 = self.trainer.steps  # nonzero after a resume
        self._epoch_episodes0 = 0
        self._trainer_thread: Optional[threading.Thread] = None

        # -- preemption-safe drain (docs/fault_tolerance.md) --------------
        # SIGTERM (how TPU VMs are preempted) / SIGINT install a stop flag:
        # the pipelines drain, a final manifest-verified checkpoint lands
        # under drain_deadline_seconds, and run() returns EXIT_RESUMABLE so
        # the launcher relaunches with restart_epoch: -1.
        self.drain_deadline = float(self.args.get("drain_deadline_seconds", 60.0))
        self._drain_requested = False
        self._drain_t0 = 0.0
        self._drain_stopped = False     # trainer.stop() issued for the drain
        self._prev_handlers: Dict[int, Any] = {}

        # -- device rollout plane (runtime/rollout_plane.py) ---------------
        # fully on-device self-play: env stepping + inference + sampling in
        # one jit call per batch of games, on a thread of its own beside
        # this server loop, with a watchdog over it; workers then mostly
        # evaluate.  Pod-slice rung 1: device_rollout_games is the GLOBAL
        # lane count; each process runs its 1/nprocs share on its LOCAL
        # devices (divisibility validated in config.py) and the shards meet
        # in the collective train step via put_batch
        self._next_update_episodes = (
            self.args["minimum_episodes"] + self.args["update_episodes"]
        )
        # per-epoch device self-play volume -> mean episode length in
        # metrics.jsonl (the survival signal on episode-length envs)
        self._device_epoch_eps = 0
        self._device_epoch_steps = 0
        self.rollout: Optional[RolloutPlane] = None
        games = int(self.args.get("device_rollout_games", 0)) // self._dist_nprocs
        if games > 0:
            # constructed HERE so misconfiguration fails the run at startup
            # instead of silently killing the rollout daemon thread
            self.rollout = RolloutPlane(
                self.env, self.module, self.args, games,
                self.trainer.ctx.mesh, self._actor_mesh, self._dist_rank,
                live=lambda: not self.shutdown_flag,
                budget_met=lambda: (
                    self.num_returned_episodes >= self._next_update_episodes
                ),
                snapshot=self.model_server.latest_snapshot,
                steps=lambda: self.trainer.steps,
                submit=self._submit,
                set_publisher=lambda cache: setattr(
                    self.trainer, "param_cache", cache
                ),
            )
            self.trainer.device_replay = self.rollout.replay
            if self.trainer.param_cache is not None:
                # version 0 .. steps: the resumed step count keeps publish
                # versions monotone across restarts
                self.trainer.param_cache.publish(
                    self.trainer.state["params"], self.trainer.steps
                )

        # on-device evaluation (runtime/device_eval.py): batched
        # net-vs-baseline matches at every epoch boundary — the per-epoch
        # win-rate curve that host eval workers starve on 1-core hosts
        # (both round-3 soaks recorded NaN/sparse curves)
        self._device_eval = None
        n_eval = int(self.args.get("device_eval_games", 0))
        if n_eval > 0:
            vector_env = getattr(self.env, "vector_env", None)
            if vector_env is None:
                raise ValueError(
                    f"device_eval_games set but env "
                    f"{args['env_args'].get('env')} exposes no vector_env()"
                )
            venv = vector_env()
            opp_list = self.args.get("eval", {}).get("opponent") or ["random"]
            if not isinstance(opp_list, list):  # same coercion as Evaluator
                opp_list = [opp_list]
            opp = opp_list[0]
            if opp not in ("random", "rulebase") or (
                opp == "rulebase" and not hasattr(venv, "rule_based_action_all")
            ):
                # downgrading must be loud: a config asking for rulebase
                # curves would otherwise quietly chart a different opponent
                print(
                    f"[handyrl_tpu] device eval: opponent '{opp}' unavailable "
                    f"for this vector env; evaluating vs 'random' instead"
                )
                opp = "random"
            # DeviceEvaluator rejects episodic twins (no streaming
            # reset_done/step hooks) at construction — surfacing the
            # device_eval_games misconfiguration at learner startup
            from .device_eval import DeviceEvaluator

            mesh = self.trainer.ctx.mesh
            lanes = min(64, max(8, n_eval))
            dp = mesh.shape.get("dp", 1)
            lanes = max(dp, lanes - lanes % dp)
            self._device_eval = DeviceEvaluator(
                venv, self.module, n_lanes=lanes, opponent=opp, mesh=mesh,
            )

    # -- subclass hooks (league/learner.py overrides these) -------------------

    def _make_model_server(self, args: Dict[str, Any]):
        """The model-id -> handle server actors resolve through; the
        league plane substitutes a ModelRouter-backed variant so frozen
        opponents get resident engines on distinct chips."""
        return LocalModelServer(self.module, make_env(args["env_args"]), self.args)

    def _epoch_hook(self, record: Dict[str, Any]) -> None:
        """Called at each epoch boundary just before the metrics record is
        written (snapshot for the new epoch already saved) — subsystems add
        their per-epoch bookkeeping/metrics here."""

    def _gc_pinned(self):
        """Epochs checkpoint GC must never collect (beyond the newest
        verified snapshot, which gc_snapshots always pins): the league pins
        its frozen population members here."""
        return ()

    def _gc_pin_set(self):
        """The full pin set every gc_snapshots call site passes: the
        subclass pins (league population) UNION the epochs the serving
        tier reports it is routing (SERVING.json — latest, a staged
        candidate, and the live incumbent).  A gated candidate can trail
        ``keep_checkpoints`` behind while the serving plane still needs
        its incumbent as the demote/rollback target; collecting it would
        turn a quality demote into a restart-from-nothing."""
        from ..flywheel.quality import serving_pinned_epochs

        pins = set(self._gc_pinned())
        pins |= serving_pinned_epochs(self.model_dir)
        return tuple(sorted(pins))

    def _flywheel_epoch(self, record: Dict[str, Any]) -> None:
        """Epoch-boundary flywheel bookkeeping: fold the harvest-ingest
        counters into the metrics record and consume any NEW quality-plane
        rollback signal (seq-gated — each signal is applied exactly once)
        by asking the trainer to roll back on its own thread."""
        if not self._flywheel_cfg.get("enabled"):
            return
        if self._flywheel_ingestor is not None:
            record.update(self._flywheel_ingestor.stats())
        from ..flywheel import read_rollback_signal

        sig = read_rollback_signal(self.model_dir)
        seq = int(sig.get("seq", 0)) if sig else 0
        if sig and seq > self._flywheel_rollback_seq:
            self._flywheel_rollback_seq = seq
            target = int(sig.get("target_epoch", 0))
            print(
                f"flywheel: serving tier flagged epoch "
                f"{sig.get('bad_epoch')} ({sig.get('reason')}); requesting "
                f"trainer rollback to verified epoch {target or 'newest'}"
            )
            self.trainer.request_rollback(target)
            self.flywheel_rollbacks += 1
        record["flywheel_rollbacks"] = self.flywheel_rollbacks

    # -- request plumbing ---------------------------------------------------

    def _submit(self, req: str, data: Any) -> Future:
        """A request onto the server loop's queue; the loop answers through
        the Future (the rollout plane waits on it with patience, the plane
        gateway's serve thread does not wait at all)."""
        fut: Future = Future()
        self._requests.put((req, data, fut))
        return fut

    def handle(self, req: str, data: Any, timeout: Optional[float] = None) -> Any:
        """Thread-safe entry point for workers; blocks until served (or
        until ``timeout``)."""
        return self._submit(req, data).result(timeout=timeout)

    # -- bookkeeping (train.py:457-500) -------------------------------------

    def feed_episodes(self, episodes: List[Optional[Dict]]) -> None:
        for episode in episodes:
            if episode is None:
                continue
            for p in episode["args"]["player"]:
                model_id = episode["args"]["model_id"][p]
                outcome = episode["outcome"][p]
                n, r, r2 = self.generation_results.get(model_id, (0, 0, 0))
                self.generation_results[model_id] = n + 1, r + outcome, r2 + outcome ** 2
            self.num_returned_episodes += 1
            if self.num_returned_episodes % 100 == 0:
                print(self.num_returned_episodes, end=" ", flush=True)
        self.trainer.store.extend(episodes)

    def feed_results(self, results: List[Optional[Dict]]) -> None:
        for result in results:
            if result is None:
                continue
            for p in result["args"]["player"]:
                model_id = result["args"]["model_id"][p]
                res = result["result"][p]
                n, r, r2 = self.results.get(model_id, (0, 0, 0))
                self.results[model_id] = n + 1, r + res, r2 + res ** 2
                per_opp = self.results_per_opponent.setdefault(model_id, {})
                n, r, r2 = per_opp.get(result["opponent"], (0, 0, 0))
                per_opp[result["opponent"]] = n + 1, r + res, r2 + res ** 2

    # -- epoch boundary (train.py:502-538) -----------------------------------

    def _win_rate(self, stats) -> tuple:
        n, r, _ = stats
        mean = r / (n + 1e-6)
        return (mean + 1) / 2, n

    def _feed_device_eval(self) -> None:
        """Batched on-device matches with the current snapshot, filed into
        the same books as worker eval results (so _win_rate and the
        metrics.jsonl win_rate curve see them unchanged)."""
        import jax

        epoch, params = self.model_server.latest_snapshot()
        key = jax.random.PRNGKey(self.args["seed"] + 0xE7A1 + self.model_epoch)
        with trace_span("eval.device", plane="eval", epoch=self.model_epoch):
            counts = self._device_eval.evaluate(
                params, int(self.args["device_eval_games"]), key
            )
        opponent = "device-" + self._device_eval.opponent
        self.feed_results([
            {"args": {"player": [0], "model_id": {0: epoch}},
             "result": {0: outcome}, "opponent": opponent}
            for outcome, n in counts.items() for _ in range(n)
        ])

    def update(self) -> None:
        print()
        print("epoch %d" % self.model_epoch)
        record: Dict[str, Any] = {"epoch": self.model_epoch}

        if self._device_eval is not None:
            self._feed_device_eval()

        if self.model_epoch not in self.results:
            # no eval results this epoch: an explicit null record (tooling
            # can chart the gap) instead of the old misspelled "Nan" stdout
            # placeholder no parser ever matched
            print("win rate = n/a (0 games)")
            record["win_rate"] = None
        else:
            def output_wp(name, stats):
                wr, n = self._win_rate(stats)
                tag = " (%s)" % name if name else ""
                print("win rate%s = %.3f (%.1f / %d)" % (tag, wr, wr * n, n))
                record.setdefault("win_rate", {})[name or "total"] = wr

            per_opp = self.results_per_opponent.get(self.model_epoch, {})
            if len(self.args.get("eval", {}).get("opponent", [])) <= 1 and len(per_opp) <= 1:
                output_wp("", self.results[self.model_epoch])
            else:
                output_wp("total", self.results[self.model_epoch])
                for key in sorted(per_opp):
                    output_wp(key, per_opp[key])

        if self.model_epoch not in self.generation_results:
            print("generation stats = n/a (0 episodes)")
            record["generation_mean"] = None
        else:
            n, r, r2 = self.generation_results[self.model_epoch]
            mean = r / (n + 1e-6)
            std = max(r2 / (n + 1e-6) - mean ** 2, 0.0) ** 0.5
            print("generation stats = %.3f +- %.3f" % (mean, std))
            record["generation_mean"] = mean
            record["generation_std"] = std

        with trace_span("epoch.snapshot_wait", plane="learner"):
            params, steps = self.trainer.update()
        if params is None:
            params = self.model_server.latest_params()
        self.update_model(params, steps)

        if self.trainer.last_loss:
            record["loss"] = dict(self.trainer.last_loss)
        if self.trainer.stats:
            record.update(self.trainer.stats)
        if self.trainer.device_replay is None:
            # read the LIVE mode: an shm pipeline that degraded to
            # threads after batcher deaths must not be recorded as shm
            try:
                record["pipeline"] = self.trainer.batcher.stats()["mode"]
            except Exception:
                record["pipeline"] = self.batch_pipeline_mode
        now = time.time()
        record.update(
            steps=steps,
            episodes=self.num_returned_episodes,
            episodes_per_sec=(self.num_returned_episodes - self._epoch_episodes0) / max(now - self._epoch_t0, 1e-6),
            updates_per_sec=(steps - self._epoch_steps0) / max(now - self._epoch_t0, 1e-6),
        )
        if self._device_epoch_eps:
            record["device_mean_episode_len"] = self._device_epoch_steps / self._device_epoch_eps
            self._device_epoch_eps = 0
            self._device_epoch_steps = 0
        if self.rollout is not None:
            record.update(self.rollout.books())
        substituted = getattr(self.model_server, "substituted_snapshots", 0)
        if substituted:
            # cumulative: N old-snapshot requests were served LATEST params
            # instead (missing/corrupt file) — eval results attributed to
            # those epochs are suspect, and the books must say so
            record["serve_snapshot_substituted"] = substituted
        if self._dist_nprocs > 1:
            # cross-host health (cumulative, like the other event
            # counters): nonzero anywhere in the run means the plane saw
            # trouble — the final pre-exit values ride the host-fault
            # drain record instead, since a drained process never reaches
            # another boundary
            record["dist_processes"] = self._dist_nprocs
            record.update(self._dist_events())
            if self._health is not None and self._rank_metrics:
                snap = self._rank_snapshot(steps)
                if self._dist_follower:
                    # PR 12 made metrics.jsonl coordinator-only; the
                    # snapshot rides the next heartbeat ack round so THIS
                    # rank shows up in the coordinator's rank_* aggregates
                    self._health.offer_metrics(snap)
                else:
                    record.update(self._health.rank_aggregates(snap))
        if trace.enabled():
            # tracer health next to the data it may be dropping: a nonzero
            # trace_dropped means the ring was outrun this run
            record.update(trace.trace_stats())
        if self.rollout is not None:
            record.update(
                self.rollout.epoch_stats(max(now - self._epoch_t0, 1e-6))
            )
        self._epoch_t0 = now
        self._epoch_steps0 = steps
        self._epoch_episodes0 = self.num_returned_episodes
        self._flywheel_epoch(record)
        self._epoch_hook(record)
        self._write_metrics(record)

    def update_model(self, params, steps: int) -> None:
        print("updated model(%d)" % steps)
        self.model_epoch += 1
        self._dist_fault_hooks()
        save_params = params
        if self._fault_poison_epoch is not None \
                and self.model_epoch == self._fault_poison_epoch:
            # fault injection (runtime/faults.py): the SAVED snapshot is
            # sabotaged — negated params are digest-valid and load cleanly,
            # so only the flywheel's live quality gate can catch it.  The
            # in-memory/published params stay clean: training is healthy,
            # the artifact is the lie.
            from ..utils import tree_map

            print(f"[fault] poison_snapshot: epoch {self.model_epoch} "
                  "snapshot saved with NEGATED params (training params "
                  "stay clean)", flush=True)
            save_params = tree_map(lambda x: -x, params)
        if is_coordinator():
            # process-0 guard: under jax.distributed every process runs the
            # SPMD train step, but exactly one owns the checkpoint files.
            # Every file goes tmp -> fsync -> rename and lands in the CRC
            # manifest, so a crash at ANY instant leaves the previous
            # epoch's resume point intact and verifiable.
            with trace_span("checkpoint.save", plane="learner",
                            epoch=self.model_epoch):
                save_epoch_snapshot(
                    self.model_dir,
                    self.model_epoch,
                    save_params,
                    self.trainer.save_payload(self.model_epoch),
                    steps,
                )
                gc_snapshots(
                    self.model_dir,
                    int(self.args.get("keep_checkpoints", 0)),
                    pin=self._gc_pin_set(),
                )
        self.model_server.publish(self.model_epoch, params)

    def _repair_metrics_tail(self, path: str) -> None:
        """Drop a half-written final line left by a killed run BEFORE the
        resumed run appends to it: appending onto a truncated tail would
        glue two records into one mid-file invalid line, which readers
        rightly refuse (read_metrics only tolerates truncation at the
        END).  Runs once per process, on the first append."""
        try:
            with open(path, "rb+") as f:
                f.seek(0, os.SEEK_END)
                size = f.tell()
                if size == 0:
                    return
                f.seek(-1, os.SEEK_END)
                if f.read(1) == b"\n":
                    return
                back = min(size, 1 << 20)
                f.seek(size - back)
                cut = f.read(back).rfind(b"\n")
                f.truncate(size - back + cut + 1 if cut >= 0 else 0)
            print(
                f"[handyrl_tpu] {path}: dropped a truncated final line "
                "(half-written record from a killed run) before appending",
                file=sys.stderr,
            )
        except OSError:
            pass  # unreadable/missing file: the append below will surface it

    def _write_metrics(self, record: Dict[str, Any]) -> None:
        """Crash-safe metrics append: ONE write() per record (a single
        O_APPEND write of under a pipe-buffer's worth lands contiguously),
        flushed AND fsynced before returning, so a kill at any instant
        costs at most the final line — and readers tolerate exactly that
        (utils.metrics.read_metrics skips a truncated tail)."""
        path = self.args.get("metrics_path")
        if not path or not is_coordinator():
            return
        if not getattr(self, "_metrics_tail_checked", False):
            self._metrics_tail_checked = True
            if os.path.exists(path):
                self._repair_metrics_tail(path)
        # the ONE timestamp seam: every record carries wall-clock ts (cross
        # -run/cross-host alignment, absolute) and t_mono (monotonic — rate
        # math immune to NTP steps), so tooling stops using the record
        # index as a time axis (scripts/_logparse.py time_axis)
        record.setdefault("ts", round(time.time(), 6))
        record.setdefault("t_mono", round(time.monotonic(), 6))
        line = json.dumps(record, default=float) + "\n"
        with open(path, "a") as f:
            f.write(line)
            f.flush()
            try:
                os.fsync(f.fileno())
            except OSError:
                pass  # metrics durability is best-effort on exotic mounts

    # -- server loop (train.py:540-626) --------------------------------------

    def _assign_role(self) -> Dict[str, Any]:
        args: Dict[str, Any] = {"model_id": {}}
        # device_replay: generation lives entirely on device (host episodes
        # could not enter the ring buffers — they would be stored but never
        # trained on, while racing the epoch cadence), so host workers
        # evaluate only
        if self.trainer.device_replay is not None or self.num_results < self.eval_rate * self.num_episodes:
            args["role"] = "e"
            players = self.env.players()
            me = players[self.num_results % len(players)]
            args["player"] = [me]
            args["model_id"] = {p: (self.model_epoch if p == me else -1) for p in players}
            self.num_results += 1
        else:
            args["role"] = "g"
            args["player"] = self.env.players()
            args["model_id"] = {p: self.model_epoch for p in self.env.players()}
            self.num_episodes += 1
        return args

    def _workers_active(self) -> bool:
        """Drain condition: remote counts live connections, local counts threads."""
        if self.remote:
            if self._shutdown_t0 and time.time() - self._shutdown_t0 > 30.0:
                return False  # grace period for lingering connections
            return self.worker.connection_count() > 0
        return self._active_workers > 0

    # -- preemption-safe drain ------------------------------------------------

    def _drain_handler(self, signum, frame) -> None:
        """SIGTERM/SIGINT: install the stop flag and let the loops drain.
        Runs on the main thread (the server loop), so it only flips flags;
        the heavy lifting happens at the next loop iteration.  A second
        signal while draining is ignored (supervisors often double-tap)."""
        if self._drain_requested:
            return
        self._drain_requested = True
        self._drain_t0 = time.time()
        self.shutdown_flag = True
        try:
            name = signal.Signals(signum).name
        except ValueError:
            name = str(signum)
        print(
            f"[handyrl_tpu] {name} received: draining (final verified "
            f"checkpoint within {self.drain_deadline:.0f}s, then exit "
            f"{EXIT_RESUMABLE} for a restart_epoch: -1 relaunch)",
            file=sys.stderr,
        )

    def _install_signal_handlers(self) -> None:
        """Only the main thread may install handlers; elsewhere (a Learner
        driven from a test/helper thread) the drain is still reachable by
        calling _drain_handler directly."""
        if threading.current_thread() is not threading.main_thread():
            return
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, self._drain_handler)
            except (ValueError, OSError):  # embedded interpreters
                pass

    def _restore_signal_handlers(self) -> None:
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        self._prev_handlers = {}

    def _drain_tick(self) -> bool:
        """Per-iteration drain bookkeeping; True = force the loop to end
        (deadline exhausted with workers still attached)."""
        if not self._drain_requested:
            return False
        if not self._drain_stopped:
            self._drain_stopped = True
            # stop the trainer mid-epoch: its thread snapshots state_host
            # on the way out, which becomes the drain checkpoint.  Multi-
            # process, this is cadence-aware (Trainer.request_drain): the
            # coordinator broadcasts the DRAIN bit so every process ends
            # the epoch together instead of wedging the peers mid-collective
            self.trainer.request_drain()
        if time.time() - self._drain_t0 > self.drain_deadline:
            print(
                "[handyrl_tpu] drain deadline exceeded; forcing shutdown "
                "(the checkpoint still lands from the last consistent state)",
                file=sys.stderr,
            )
            return True
        return False

    def _write_drain_checkpoint(self) -> None:
        """The drain's final durable save: epoch snapshot + state + manifest
        entry via the same atomic path as every boundary save, so
        ``restart_epoch: -1`` verifies and resumes it."""
        if not is_coordinator():
            return
        self.model_epoch += 1
        params, payload, steps = self.trainer.drain_payload(self.model_epoch)
        save_epoch_snapshot(self.model_dir, self.model_epoch, params, payload, steps)
        gc_snapshots(
            self.model_dir,
            int(self.args.get("keep_checkpoints", 0)),
            pin=self._gc_pin_set(),
        )
        print(
            f"[handyrl_tpu] drain checkpoint: epoch {self.model_epoch} at "
            f"step {steps} (manifest-verified; resume with restart_epoch: -1)",
            file=sys.stderr,
        )

    # -- cross-host fault handling (parallel/health.py) -----------------------

    def _rank_snapshot(self, steps: Optional[int] = None) -> Dict[str, Any]:
        """This rank's per-epoch metric snapshot for the cross-host relay
        (parallel/health.py): the fields the coordinator folds into the
        rank_* aggregates.  Small on purpose — it rides heartbeat lines."""
        stats = self.trainer.stats or {}
        return {
            "epoch": self.model_epoch,
            "steps": int(self.trainer.steps if steps is None else steps),
            "train_steps_per_sec": stats.get("train_steps_per_sec"),
            "input_wait_frac": stats.get("input_wait_frac"),
        }

    def _dist_events(self) -> Dict[str, int]:
        """Cumulative cross-host health counters for the dist_* metrics."""
        health_ev = self._health.events if self._health is not None else {}
        return {
            "dist_heartbeat_misses": int(health_ev.get("heartbeat_misses", 0)),
            "dist_collective_timeouts": 1 if (
                self._collective_watchdog is not None
                and self._collective_watchdog.fired
            ) else 0,
            "dist_peer_loss_drains": int(health_ev.get("peer_losses", 0))
            + int(health_ev.get("coordinator_losses", 0)),
        }

    def _disarm_host_fault(self) -> None:
        """Called by the trainer the moment the agreed stop/drain broadcast
        returns: every rank is past its last collective, so the detectors
        must stand down before rank-skewed teardown starts."""
        if self._health is not None:
            self._health.disarm()
        if self._collective_watchdog is not None:
            self._collective_watchdog.stop()

    def _host_fault(self, reason: str, kind: str) -> None:
        """A peer process is lost or a collective wedged: runs on a health/
        watchdog thread while the trainer may be stuck inside a collective
        that can NEVER complete — no Python-level cancel exists for an
        in-flight XLA collective, so the only bounded recovery is to
        drain-save from the last consistent HOST snapshot (state_host is
        swapped atomically at each epoch end and never device-resident)
        and leave via os._exit: the normal interpreter teardown would
        block on the wedged thread.  Exit code 75 (EX_TEMPFAIL) tells the
        supervisor to relaunch every rank with restart_epoch: -1."""
        from ..parallel.health import announce_fault

        if self._host_faulted:
            return
        self._host_faulted = True
        announce_fault(reason, kind, EXIT_RESUMABLE)
        try:
            if is_coordinator():
                record = {"epoch": self.model_epoch, "dist_processes": self._dist_nprocs}
                record.update(self._dist_events())
                if self._health is not None and self._rank_metrics:
                    # last known per-rank picture rides the final record: a
                    # wedged-but-heartbeating peer shows up here as a stale
                    # epoch / grown report age — the post-mortem pointer
                    try:
                        record.update(
                            self._health.rank_aggregates(self._rank_snapshot())
                        )
                    except Exception:
                        pass  # the drain save must land regardless
                self._write_metrics(record)
                self._write_drain_checkpoint()
        except Exception:
            import traceback

            traceback.print_exc()
            print(
                "[handyrl_tpu] host-fault drain save failed (above); the "
                "previous epoch's verified checkpoint remains the resume "
                "point",
                file=sys.stderr,
            )
        sys.stderr.flush()
        sys.stdout.flush()
        os._exit(EXIT_RESUMABLE)

    def _dist_fault_hooks(self) -> None:
        """Host-loss fault injections, checked at each epoch publish
        (runtime/faults.py): rank-scoped hard kill / freeze."""
        kill = self._fault_kill_proc
        if kill is not None and self.model_epoch >= kill[0] and self._dist_rank == kill[1]:
            print(
                f"[fault] killing process rank {self._dist_rank} at epoch "
                f"{self.model_epoch} (HANDYRL_FAULT_KILL_PROCESS_AT_EPOCH)",
                file=sys.stderr,
            )
            sys.stderr.flush()
            os._exit(1)
        wedge = self._fault_wedge_proc
        if wedge is not None and self.model_epoch >= wedge[0] and self._dist_rank == wedge[1]:
            print(
                f"[fault] wedging process rank {self._dist_rank} at epoch "
                f"{self.model_epoch} (HANDYRL_FAULT_WEDGE_PROCESS): "
                "heartbeats stop, collectives stop, threads stay up",
                file=sys.stderr,
            )
            sys.stderr.flush()
            if self._health is not None:
                self._health.stop_heartbeats()
            self.trainer._fault_wedge_process = True
            while True:  # the frozen host never comes back
                time.sleep(60.0)

    def server(self) -> None:
        print("started server")
        prev_update_episodes = self.args["minimum_episodes"]
        next_update_episodes = prev_update_episodes + self.args["update_episodes"]
        self._shutdown_t0 = 0.0

        while self._workers_active() or not self.shutdown_flag:
            if self.trainer.failed:
                raise RuntimeError(
                    "the training plane stopped on an error (traceback above)"
                )
            if self._drain_tick():
                break
            if self.shutdown_flag and not self._shutdown_t0:
                self._shutdown_t0 = time.time()
            try:
                req, data, fut = self._requests.get(timeout=0.3)
            except queue.Empty:
                continue

            if req == "args":
                # data None: one local worker; int n: a gather prefetching n
                if self.shutdown_flag:
                    fut.set_result(None)
                    self._active_workers -= 1
                elif data is None:
                    fut.set_result(self._assign_role())
                else:
                    fut.set_result([self._assign_role() for _ in range(int(data))])
            elif req == "episode":
                self.feed_episodes([data] if not isinstance(data, list) else data)
                fut.set_result(None)
            elif req == "device_episodes":
                # on-device generation bypasses role assignment; count the
                # episodes so the eval_rate balance still sees them
                self.feed_episodes(data)
                self.num_episodes += len(data)
                fut.set_result(None)
            elif req == "device_counts":
                # device-replay mode: episodes never materialize on host —
                # the rollout thread reports ingest counters instead, which
                # feed the same books (epoch cadence, generation stats,
                # eval_rate balance) as feed_episodes would
                n, P = data["episodes"], data["players"]
                st = self.generation_results.get(data["model_id"], (0, 0, 0))
                self.generation_results[data["model_id"]] = (
                    st[0] + n * P,
                    st[1] + data["outcome_sum"],
                    st[2] + data["outcome_sq_sum"],
                )
                self.num_returned_episodes += n
                self.num_episodes += n
                self._device_epoch_eps += n
                self._device_epoch_steps += data.get("game_steps", 0)
                fut.set_result(None)
            elif req == "result":
                self.feed_results([data] if not isinstance(data, list) else data)
                fut.set_result(None)
            elif req == "jobs_lost":
                # a worker connection vanished with jobs in flight: hand
                # their counts back so the generation/evaluation balance
                # re-dispatches equivalents to the surviving workers
                self.num_episodes = max(0, self.num_episodes - int(data.get("g", 0)))
                self.num_results = max(0, self.num_results - int(data.get("e", 0)))
                fut.set_result(None)
            elif req == "model":
                fut.set_result(self.model_server.get(data))
            else:
                fut.set_result(None)

            if self._dist_follower:
                # coordinator-driven boundary: the trainer's queue only
                # holds a snapshot once the coordinator ended the epoch on
                # EVERY process (DistributedCadence); local episode counts
                # play no cadence role on a follower
                if self.trainer.drain_agreed and not self._drain_requested:
                    # the coordinator broadcast a preemption drain: adopt
                    # it locally so this rank also lands on EXIT_RESUMABLE
                    self._drain_requested = True
                    self._drain_t0 = time.time()
                    self.shutdown_flag = True
                    print(
                        "[handyrl_tpu] coordinator-agreed drain: shutting "
                        f"down within {self.drain_deadline:.0f}s and exiting "
                        f"{EXIT_RESUMABLE} for the coordinated relaunch",
                        file=sys.stderr,
                    )
                elif (
                    not self._drain_requested
                    and not self.trainer.update_queue.empty()
                ):
                    self.update()
                elif (
                    self.trainer.finished
                    and self.trainer.update_queue.empty()
                    and not self._drain_requested
                ):
                    # the stop was agreed through the cadence; the final
                    # snapshot above has been consumed — drain the workers
                    self.shutdown_flag = True
            elif (
                self.num_returned_episodes >= next_update_episodes
                and not self._drain_requested  # draining: no new boundary work
            ):
                prev_update_episodes = next_update_episodes
                next_update_episodes = prev_update_episodes + self.args["update_episodes"]
                self._next_update_episodes = next_update_episodes
                if self._dist_nprocs > 1 and not self.trainer._warmed_up():
                    # multi-process coordinator, PRE-WARMUP boundary:
                    # followers only ever see AGREED epoch ends (their
                    # boundary is the cadence snapshot), so counting an
                    # epoch here would advance model_epoch on this rank
                    # alone — desyncing the epochs-limit shutdown (the
                    # stop is never broadcast pre-warmup) and the
                    # rank-scoped "E:R" fault injections.  Defer it.
                    continue
                self.update()
                shutdown = (
                    self.args["epochs"] >= 0
                    and self.model_epoch >= self.args["epochs"]
                )
                # multi-process coordinator: release the trainer's post-
                # epoch handshake with the continue/shutdown decision so
                # every process stops (or starts the next epoch) together;
                # a no-op single-process and on pre-warmup boundaries
                self.trainer.proceed(shutdown)
                if shutdown:
                    self.shutdown_flag = True
        self.trainer.stop()
        self.model_server.stop()
        # resolve any futures enqueued after the loop's final iteration
        # (e.g. the device-rollout thread racing shutdown) — a blocked
        # handle() would otherwise leak a permanently waiting thread
        while True:
            try:
                _, _, fut = self._requests.get_nowait()
            except queue.Empty:
                break
            if not fut.done():
                fut.set_result(None)
        if self._trainer_thread is not None:
            # under a drain, the join is bounded by what's left of the
            # deadline (floor 5s) so a wedged trainer can't eat the budget;
            # the checkpoint then falls back to the last consistent state.
            # Multi-process the bound is wider: the thread may still be
            # inside the final agree_stop broadcast (waiting on a slower
            # rank), and leaving for jax.distributed.shutdown before it
            # returns abandons the peers inside the collective
            timeout = 120.0 if self._dist_nprocs > 1 else 30.0
            if self._drain_requested:
                left = self.drain_deadline - (time.time() - self._drain_t0)
                timeout = max(5.0, min(timeout, left))
            self._trainer_thread.join(timeout=timeout)
        if self._drain_requested:
            self._write_drain_checkpoint()
        print("finished server")

    def run(self) -> int:
        """Run to completion.  Returns 0 on a normal finish, EXIT_RESUMABLE
        (75) after a preemption-safe drain — callers (train_main) exit with
        it so the launcher knows a verified resume point is waiting."""
        self._install_signal_handlers()
        try:
            if self._health is not None:
                self._health.start()
            if self._collective_watchdog is not None:
                self._collective_watchdog.start()
            self._trainer_thread = threading.Thread(
                target=self.trainer.run, daemon=True, name="trainer"
            )
            self._trainer_thread.start()
            self.worker.run()
            self._active_workers = len(getattr(self.worker, "threads", [])) or self.args["worker"]["num_parallel"]
            if self.rollout is not None:
                self.rollout.start()
            self._start_flywheel_ingest()
            self.server()
            if self.rollout is not None:
                # let an in-flight device call drain, the watchdog with it;
                # under a drain the wait is bounded by the remaining deadline
                timeout = 120.0
                if self._drain_requested:
                    left = self.drain_deadline - (time.time() - self._drain_t0)
                    timeout = max(5.0, min(120.0, left))
                self.rollout.stop(timeout)
        finally:
            if self._flywheel_ingestor is not None:
                self._flywheel_ingestor.stop()
            if self._health is not None:
                self._health.stop()
            if self._collective_watchdog is not None:
                self._collective_watchdog.stop()
            if self.rollout is not None:
                self.rollout.close()
            self._restore_signal_handlers()
            trace.shutdown()  # flush the span ring tail; a no-op when off
        return EXIT_RESUMABLE if self._drain_requested else 0

    def _start_flywheel_ingest(self) -> None:
        """Arm the harvest-ingest poll loop (flywheel/ingest.py) when the
        flywheel is on and the mix wants served episodes.  Coordinator
        only: harvested episodes enter through feed_episodes, and under
        jax.distributed exactly one process drives the episode cadence."""
        cfg = self._flywheel_cfg
        if not cfg.get("enabled") or not is_coordinator():
            return
        if float(cfg.get("harvest_fraction", 0.5)) <= 0.0:
            return
        from ..flywheel import HarvestIngestor

        host = str(cfg.get("harvest_host", "127.0.0.1"))
        port = int(cfg.get("harvest_port", 0)) or int(
            (self.args.get("serving") or {}).get("port", 9997)
        )

        def make_client():
            from ..serving.client import ServingClient

            return ServingClient(host, port, timeout=10.0)

        def submit(episodes):
            # ride the standard request queue: feed_episodes books the
            # generation stats and drives the epoch cadence exactly as a
            # worker's self-play batch would
            self.handle("episode", episodes, timeout=60.0)

        self._flywheel_ingestor = HarvestIngestor(
            dict(cfg, update_episodes=self.args.get("update_episodes", 0)),
            submit,
            lambda: self.model_epoch,
            make_client,
        ).start()
        print(f"flywheel: harvest ingest armed ({host}:{port}, "
              f"fraction {cfg.get('harvest_fraction', 0.5)})")

    @property
    def shutdown_coherent(self) -> bool:
        """True when every process reached (or will reach) the same run
        end, so the synchronized ``jax.distributed.shutdown`` barrier is
        safe to join: a clean finish or a cadence-AGREED drain.  False
        after a follower-local drain (its SIGTERM never rode a broadcast)
        — the peers are still running or leaving via ``_host_fault``'s
        ``os._exit``, so they never join the barrier, and waiting in it
        would end in the coordination service's SIGABRT instead of the
        promised exit 75 (docs/fault_tolerance.md, one-rank SIGTERM row)."""
        if self._dist_nprocs <= 1 or not self._drain_requested:
            return True
        return bool(getattr(self.trainer, "drain_agreed", False))


def train_main(args: Dict[str, Any], remote: bool = False) -> None:
    from ..parallel.distributed import shutdown_distributed

    learner = Learner(args, remote=remote)
    code = learner.run()
    if learner.shutdown_coherent:
        shutdown_distributed()
    if code:
        sys.exit(code)


def train_server_main(args: Dict[str, Any]) -> None:
    train_main(args, remote=True)
