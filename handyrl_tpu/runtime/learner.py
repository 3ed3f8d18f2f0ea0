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
from concurrent.futures import TimeoutError as FutureTimeoutError  # plain Exception subclass until py3.11
from typing import Any, Dict, List, Optional

from ..envs import make_env, prepare_env
from ..models import init_variables
from ..parallel import is_coordinator, make_mesh
from ..utils import trace
from ..utils.trace import trace_span
from . import faults
from .checkpoint import (
    gc_snapshots,
    latest_verified_epoch,
    load_verified_params,
    save_epoch_snapshot,
    verify_state,
)
from .trainer import Trainer
from .worker import LocalModelServer, LocalWorkerPool

# Exit status after a preemption-safe drain (SIGTERM/SIGINT): the run
# stopped with a VERIFIED resume point on disk and wants to be relaunched
# with ``restart_epoch: -1``.  75 = BSD EX_TEMPFAIL ("temporary failure,
# retry"), the conventional please-reschedule-me code supervisors honor.
EXIT_RESUMABLE = 75

# cumulative plane-watchdog event counters in metrics.jsonl (same
# convention as pipe_batcher_* / sentinel_*: rare events diffed per epoch
# would mostly print zeros)
WATCHDOG_EVENT_KEYS = (
    "plane_watchdog_stalls",
    "plane_watchdog_restarts",
    "plane_watchdog_degraded",
)


class Learner:
    def __init__(self, args: Dict[str, Any], net=None, remote: bool = False):
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
        self._plane = self.args.get("plane", "fused")
        self._actor_mesh = None
        self._param_cache = None       # versioned params on the actor mesh
        self._record_xfer = None       # actor -> learner record transfer
        self._plane_stats = None
        self._plane_stats0: Dict[str, float] = {}
        if self._plane == "split":
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

        # -- plane watchdog ------------------------------------------------
        # Liveness supervision of the device-rollout plane: a rollout
        # thread that dies or stops making progress for plane_stall_timeout
        # (or actor params lagging past plane_param_lag_bound) is restarted
        # up to plane_max_restarts times; past the budget a split-plane run
        # degrades split -> fused LOUDLY (the shm-batcher degrade pattern).
        self._rollout_thread: Optional[threading.Thread] = None
        self._rollout_gen = 0           # generation token: stale loops exit
        self._rollout_progress_t = time.monotonic()
        self._watchdog_events: Dict[str, int] = {k: 0 for k in WATCHDOG_EVENT_KEYS}
        self._fault_wedge = faults.wedge_rollout()

        # fully on-device self-play (runtime/device_rollout.py): env
        # stepping + inference + sampling in one jit call per batch of
        # games; workers then mostly evaluate
        self._device_games = int(self.args.get("device_rollout_games", 0))
        if self._dist_nprocs > 1 and self._device_games > 0:
            # pod-slice rung 1: device_rollout_games is the GLOBAL lane
            # count; each process runs its 1/nprocs share on its LOCAL
            # devices (divisibility validated in config.py) and the
            # shards meet in the collective train step via put_batch
            self._device_games //= self._dist_nprocs
        self._replay = None        # set below in device_replay mode
        self._data_mesh = None     # local mesh the data plane runs on
        self._plane_gateway = None  # rung-2 actor-host transport (run())
        # per-epoch device self-play volume -> mean episode length in
        # metrics.jsonl (the survival signal on episode-length envs)
        self._device_epoch_eps = 0
        self._device_epoch_steps = 0
        self._next_update_episodes = (
            self.args["minimum_episodes"] + self.args["update_episodes"]
        )
        if self._plane == "split" and self._device_games <= 0:
            raise ValueError(
                "plane: split needs device_rollout_games > 0 (the actor "
                "plane generates with the on-device streaming rollout)"
            )
        if self._device_games > 0:
            vector_env = getattr(self.env, "vector_env", None)
            if vector_env is None:
                raise ValueError(
                    f"device_rollout_games set but env "
                    f"{args['env_args'].get('env')} exposes no vector_env()"
                )
            self._venv = vector_env()
            n_verify = int(self.args.get("autovec_verify_games", 0))
            if n_verify > 0 and getattr(self._venv, "__autovec__", False):
                # autovec-lifted twin: refuse to train on a divergent lift
                # (random-game step-parity vs the numpy rules; raises
                # AutovecError naming the diverged observable)
                self._venv.verify(n_verify, int(self.args["seed"]))
                print(
                    f"autovec twin verified: {self._venv.__name__} parity "
                    f"over {n_verify} random games"
                )
            if self._plane == "split" and not hasattr(self._venv, "record"):
                raise ValueError(
                    "plane: split needs a STREAMING vector env (record/"
                    "reset_done/step hooks) — the episodic driver runs on "
                    f"the default device, not the actor mesh; "
                    f"{getattr(self._venv, '__name__', type(self._venv).__name__)} "
                    "lacks them"
                )
            if (
                self._actor_mesh is not None
                and self._device_games % self._actor_mesh.size
            ):
                # fail HERE, not as a sharding error inside the rollout
                # daemon thread — lanes shard over the actor mesh's dp
                raise ValueError(
                    f"device_rollout_games {self._device_games} not "
                    f"divisible by actor_chips {self._actor_mesh.size} "
                    "(plane: split shards the lanes over the actor mesh)"
                )
            if self.args["observation"] and not hasattr(self._venv, "observe_mask"):
                raise ValueError(
                    "device_rollout_games with observation: true requires a "
                    "vector env that records observer views (an observe_mask "
                    f"hook); {type(self._venv).__name__ if not isinstance(self._venv, type) else self._venv.__name__} "
                    "records acting players only — use host actors instead"
                )
            # pod-slice rung 1: under multi-process SPMD the data plane
            # (rollout lanes, rings, record transfer) is PER PROCESS on
            # this host's local learner devices — only the train step is
            # collective, and the local shard it samples enters via
            # TrainContext.put_batch's make_array_from_process_local_data
            # seam.  Single-process: the data plane IS the learner mesh.
            if self._dist_nprocs > 1:
                local = [
                    d
                    for d in self.trainer.ctx.mesh.devices.flat
                    if d.process_index == jax.process_index()
                ]
                self._data_mesh = make_mesh({"dp": -1}, local)
            else:
                self._data_mesh = self.trainer.ctx.mesh
            # constructed HERE so misconfiguration (e.g. lane count not
            # divisible by the mesh's dp axis) fails the run at startup
            # instead of silently killing the rollout daemon thread
            if self.args.get("device_replay"):
                # data stays on device end to end: rollout records ->
                # ring buffers -> sampled batches -> SGD, one dispatch
                # each (runtime/device_replay.py); DeviceReplay validates
                # the env/net/config constraints here, at startup
                from .device_replay import DeviceReplay
                from .device_rollout import build_streaming_fn

                mesh = self._data_mesh
                # rings (and the ingest/train donation contract) live on
                # the LEARNER data mesh (this process's learner devices);
                # under plane: split the rollout program runs on the actor
                # mesh and its records cross over
                self._replay = DeviceReplay(
                    self._venv, self.module, self.args, mesh,
                    self._device_games,
                    slots=self.args["device_replay_slots"],
                )
                roll_mesh = (
                    self._actor_mesh
                    if self._actor_mesh is not None
                    else (mesh if mesh.size > 1 else None)
                )
                self._stream_fn = build_streaming_fn(
                    self._venv, self.module, self._device_games,
                    self.args["device_replay_k_steps"],
                    mesh=roll_mesh,
                    use_observe_mask=bool(self.args["observation"]),
                )
                self.trainer.device_replay = self._replay
                self._device_roll = None
                if self._actor_mesh is not None:
                    from .plane import RecordTransfer

                    self._record_xfer = RecordTransfer(mesh)
            else:
                from .device_rollout import make_device_rollout

                self._device_roll = make_device_rollout(
                    self._venv, self.module, self.args, self._device_games,
                    mesh=self._actor_mesh
                    if self._actor_mesh is not None
                    else self._data_mesh,
                )
            if self._actor_mesh is not None:
                from .plane import PlaneParamCache, PlaneStats

                self._param_cache = PlaneParamCache(self._actor_mesh)
                self._plane_stats = PlaneStats()
                self.trainer.param_cache = self._param_cache
            # pod-slice rung 2: the coordinator fronts the cross-host
            # plane — record batches from distributed.actor_hosts land in
            # its device rings, versioned params go back over DCN
            # (runtime/plane.py).  Followers never host it: actor hosts
            # dial the one coordinator-derived plane port.
            dist_args = self.args.get("distributed") or {}
            if int(dist_args.get("actor_hosts") or 0) > 0 and not self._dist_follower:
                if self._replay is None:
                    raise ValueError(
                        "distributed.actor_hosts > 0 needs device_replay: "
                        "true on the learner tier — actor-host record "
                        "batches land in the device replay rings "
                        "(docs/performance.md §Pod-slice topology)"
                    )
                from .plane import PlaneGateway

                self._plane_gateway = PlaneGateway(
                    dist_args,
                    on_records=self._gateway_on_records,
                    inner=self._param_cache,
                )
                # one publish surface feeds both transports: the gateway
                # delegates to the local actor-mesh cache when plane:
                # split is also active on this host
                self.trainer.param_cache = self._plane_gateway
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

    def handle(self, req: str, data: Any, timeout: Optional[float] = None) -> Any:
        """Thread-safe entry point for workers; blocks until served (or
        until ``timeout`` — used by the device-rollout thread, whose
        submission can race server shutdown)."""
        fut: Future = Future()
        self._requests.put((req, data, fut))
        return fut.result(timeout=timeout)

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
        if self._replay is not None:
            # cumulative host ints the rollout thread already keeps: game
            # steps the rings have booked and the ingests that booked them
            record["device_game_steps"] = self._replay.counters["game_steps"]
            record["device_rollout_dispatches"] = self._replay.counters["ingests"]
        substituted = getattr(self.model_server, "substituted_snapshots", 0)
        if substituted:
            # cumulative: N old-snapshot requests were served LATEST params
            # instead (missing/corrupt file) — eval results attributed to
            # those epochs are suspect, and the books must say so
            record["serve_snapshot_substituted"] = substituted
        if self._device_games > 0:
            # live plane topology (flips split -> fused after a watchdog
            # degradation) + cumulative watchdog events
            record["plane"] = self._plane
            record.update(self._watchdog_events)
        if self._dist_nprocs > 1:
            # cross-host health (cumulative, like the other event
            # counters): nonzero anywhere in the run means the plane saw
            # trouble — the final pre-exit values ride the host-fault
            # drain record instead, since a drained process never reaches
            # another boundary
            record["dist_processes"] = self._dist_nprocs
            record.update(self._dist_events())
        if self._plane_gateway is not None:
            # cross-host actor tier health: live producer count plus the
            # cumulative losses (each one a degrade the survivors absorbed)
            record["dist_actor_hosts"] = int(self._plane_gateway.actor_hosts)
            record["dist_actor_host_losses"] = int(
                self._plane_gateway.actor_host_losses
            )
        if self._dist_nprocs > 1:
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
        # local refs: a concurrent watchdog degrade nulls these attributes
        # between the None-check and the reads (same hazard as
        # _actor_params) — the epoch record must not die on the very
        # degrade it is reporting
        plane_stats = self._plane_stats
        param_cache = self._param_cache
        record_xfer = self._record_xfer
        gateway = self._plane_gateway
        if gateway is not None or (
            plane_stats is not None and param_cache is not None
        ):
            # per-epoch plane health (diffed cumulative counters): realized
            # actor-plane duty, mean param staleness at dispatch, and the
            # cross-plane transfer rate (records learner-ward + params
            # actor-ward) — the plane_* keys soaks watch next to pipe_*.
            # The gateway's byte count already folds in the local cache
            # (``inner``), so it substitutes rather than adds.
            snap = plane_stats.snapshot() if plane_stats is not None else {}
            cache_bytes = (
                gateway.bytes_transferred
                if gateway is not None
                else param_cache.bytes_transferred
            )
            snap["xfer_bytes"] = cache_bytes + (
                record_xfer.bytes_transferred if record_xfer else 0
            )
            prev, dt = self._plane_stats0, max(now - self._epoch_t0, 1e-6)
            diff = lambda k: snap.get(k, 0.0) - prev.get(k, 0.0)
            if plane_stats is not None:
                record["plane_actor_busy_frac"] = round(diff("actor_busy_s") / dt, 4)
                record["plane_actor_idle_frac"] = round(diff("actor_idle_s") / dt, 4)
            record["plane_xfer_bytes_per_sec"] = round(diff("xfer_bytes") / dt, 1)
            if diff("actor_dispatches"):
                record["plane_param_lag_mean"] = round(
                    diff("param_lag_sum") / diff("actor_dispatches"), 2
                )
            self._plane_stats0 = snap
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
        if self._replay is not None or self.num_results < self.eval_rate * self.num_episodes:
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

    def _gateway_on_records(self, records: Dict[str, Any]) -> None:
        """Plane-gateway ingest (runs on a gateway serve thread): validate
        the lane width, ingest into this process's device rings, and book
        the counters through the same server-loop request the local
        rollout thread uses.

        ``defer=False`` on purpose: the deferred-stats FIFO belongs to the
        local rollout thread (``ingest_counted(defer=True)`` pairs each
        dispatch with a LATER fetch), and a second writer interleaving
        would misattribute both streams' stats.  One synchronous scalar
        fetch per record batch is noise next to the DCN payload it rode
        in on."""
        import jax

        widths = {x.shape[1] for x in jax.tree.leaves(records)}
        if widths != {self._device_games}:
            raise ValueError(
                f"plane gateway: record batch lane width {sorted(widths)} "
                f"!= this learner's {self._device_games} per-process lanes "
                "(device_rollout_games / num_processes must match on both "
                "tiers)"
            )
        stats = self._replay.ingest_counted(records, defer=False)
        episodes = int(stats["episodes"])
        if episodes <= 0 and int(stats["game_steps"]) <= 0:
            return
        counts = {
            "episodes": episodes,
            "players": self._venv.num_players,
            "model_id": self.model_epoch,
            "game_steps": int(stats["game_steps"]),
            "outcome_sum": float(stats["outcome_sum"].sum()),
            "outcome_sq_sum": float(stats["outcome_sq_sum"]),
        }
        # fire-and-forget: the serve thread must keep answering its actor
        # host; the server loop books the counts when it gets there
        self._requests.put(("device_counts", counts, Future()))

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

    # -- rollout plane: generation-tokened loop + watchdog --------------------

    def _start_rollout_thread(self) -> threading.Thread:
        """(Re)start the device-rollout thread under a fresh generation
        token.  A superseded generation exits at its next liveness check
        (a thread truly wedged inside a dispatch cannot be killed from
        Python — it is abandoned and its generation invalidated, which is
        the best any host-side supervisor can do)."""
        self._rollout_gen += 1
        gen = self._rollout_gen
        self._rollout_progress_t = time.monotonic()
        # stall detection arms only after this generation's FIRST dispatch
        # completes: the first call pays jit compilation (minutes for a
        # big model on TPU), and declaring that a stall would burn the
        # whole restart budget on a healthy warm-up (a thread that DIES
        # during compile is still caught by the dead-thread check)
        self._rollout_dispatched = False
        t = threading.Thread(
            target=self._device_rollout_loop, args=(gen,), daemon=True,
            name=f"device-rollout-{gen}",
        )
        self._rollout_thread = t
        t.start()
        return t

    def _rollout_live(self, gen: int) -> bool:
        return not self.shutdown_flag and self._rollout_gen == gen

    def _rollout_beat(self) -> None:
        """Progress heartbeat for the plane watchdog: every dispatch,
        backpressure sleep, and server patience-wait counts as liveness —
        only a thread that stops doing ALL of those is stalled."""
        self._rollout_progress_t = time.monotonic()

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
        while self._rollout_live(gen):
            time.sleep(0.05)  # no _rollout_beat: the watchdog must notice
        return True

    def _watchdog_loop(self) -> None:
        """Split/fused plane liveness supervision (runs whenever a device
        rollout thread exists).  Detects a dead rollout thread, a stalled
        one (no progress beat within plane_stall_timeout), or actor params
        lagging past plane_param_lag_bound; restarts the thread up to
        plane_max_restarts, then degrades split -> fused loudly."""
        timeout = float(self.args.get("plane_stall_timeout", 120.0))
        max_restarts = int(self.args.get("plane_max_restarts", 2))
        lag_bound = int(self.args.get("plane_param_lag_bound", 0))
        restarts = 0
        tick = max(0.05, min(1.0, timeout / 4.0))
        while not self.shutdown_flag:
            time.sleep(tick)
            if self.shutdown_flag or self._drain_requested:
                return
            thread = self._rollout_thread
            if thread is None:
                continue
            dead = not thread.is_alive()
            stall_s = time.monotonic() - self._rollout_progress_t
            # pre-first-dispatch silence is compile time, not a stall
            stalled = stall_s > timeout and self._rollout_dispatched
            cache = self._param_cache
            lagged = (
                lag_bound > 0
                and cache is not None
                and cache.lag(self.trainer.steps) > lag_bound
            )
            if not (dead or stalled or lagged):
                continue
            reason = (
                "thread died"
                if dead
                else f"no progress for {stall_s:.1f}s (> plane_stall_timeout)"
                if stalled
                else f"param lag {cache.lag(self.trainer.steps)} > "
                f"plane_param_lag_bound {lag_bound}"
            )
            self._watchdog_events["plane_watchdog_stalls"] += 1
            print(
                f"[handyrl_tpu] plane watchdog: rollout plane unhealthy "
                f"({reason})",
                file=sys.stderr,
            )
            if restarts < max_restarts:
                restarts += 1
                self._watchdog_events["plane_watchdog_restarts"] += 1
                print(
                    f"[handyrl_tpu] plane watchdog: restarting rollout "
                    f"thread ({restarts}/{max_restarts})",
                    file=sys.stderr,
                )
                self._start_rollout_thread()
            elif self._plane == "split":
                self._degrade_to_fused()
            else:
                print(
                    "[handyrl_tpu] plane watchdog: restart budget exhausted "
                    "on the fused plane; giving up on the rollout thread "
                    "(host actors keep generating if configured)",
                    file=sys.stderr,
                )
                return

    def _degrade_to_fused(self) -> None:
        """Split -> fused degradation (mirrors the shm-batcher degrade
        pattern): stop the cross-plane param/record flows, rebuild the
        rollout program on the LEARNER mesh, and restart the rollout
        thread there.  Training continues throughout — the learner plane
        never depended on the actor mesh."""
        self._rollout_gen += 1  # invalidate any live generation FIRST
        print(
            "[handyrl_tpu] plane watchdog: restart budget exhausted; "
            "degrading split -> fused (rollouts move to the learner mesh; "
            "cross-plane param/record flows stop)",
            file=sys.stderr,
        )
        if self._plane_gateway is not None:
            # the cross-HOST plane outlives a local split->fused degrade:
            # drop only the actor-mesh delegate, keep publishing to the
            # gateway so remote actor hosts still get fresh params
            self._plane_gateway.inner = None
            self.trainer.param_cache = self._plane_gateway
        else:
            self.trainer.param_cache = None
        self._param_cache = None
        self._record_xfer = None
        self._plane_stats = None
        self._actor_mesh = None
        self._plane = "fused"
        self._watchdog_events["plane_watchdog_degraded"] = 1
        mesh = (
            self._data_mesh
            if self._data_mesh is not None
            else self.trainer.ctx.mesh
        )
        try:
            if self._replay is not None:
                from .device_rollout import build_streaming_fn

                self._stream_fn = build_streaming_fn(
                    self._venv, self.module, self._device_games,
                    self.args["device_replay_k_steps"],
                    mesh=mesh if mesh.size > 1 else None,
                    use_observe_mask=bool(self.args["observation"]),
                )
            else:
                from .device_rollout import make_device_rollout

                self._device_roll = make_device_rollout(
                    self._venv, self.module, self.args, self._device_games,
                    mesh=mesh,
                )
        except Exception:
            import traceback

            traceback.print_exc()
            print(
                "[handyrl_tpu] plane watchdog: learner-mesh rollout rebuild "
                "failed (above); device generation stops (training continues "
                "on already-ingested data / host actors)",
                file=sys.stderr,
            )
            return
        self._start_rollout_thread()

    def _device_rollout_loop(self, gen: int) -> None:
        """Generate device self-play batches up to each epoch boundary
        (backpressure: pause once the boundary's episode budget is met, so
        the chip alternates between rollouts and train steps instead of
        flooding the store).  ``gen`` is this thread's generation token:
        the loop exits once the watchdog supersedes it."""
        import jax

        # a restarted generation must not replay the superseded stream;
        # the 1009 * rank fold decorrelates the per-process lane shares
        # (each rank generates DIFFERENT games into its local rings)
        key = jax.random.PRNGKey(
            self.args["seed"]
            + 0x5EED
            + 0x1009 * (gen - 1)
            + 1009 * self._dist_rank
        )
        if self._device_roll is None:          # device_replay mode
            try:
                self._device_replay_inner(key, gen)
            finally:
                if self._rollout_gen == gen:  # superseded: new gen owns it
                    self._replay.drain()
            return
        roll = self._device_roll
        try:
            self._device_rollout_inner(roll, key, gen)
        finally:
            # await the in-flight async dispatch; exiting the process with
            # an XLA execution still running aborts it (see
            # StreamingDeviceRollout.drain)
            if hasattr(roll, "drain") and self._rollout_gen == gen:
                roll.drain()

    def _actor_params(self):
        """(model_id, params) for the next rollout dispatch: under plane:
        split the versioned actor-mesh cache (bumping the realized-lag
        counter), else the model server's epoch snapshot."""
        cache = self._param_cache       # local refs: a concurrent watchdog
        stats = self._plane_stats       # degrade nulls these attributes
        if cache is None:
            return self.model_server.latest_snapshot()
        version, params = cache.latest()
        if stats is not None:
            stats.bump(
                actor_dispatches=1,
                param_lag_sum=max(0, self.trainer.steps - version),
            )
        return self.model_epoch, params

    def _device_replay_inner(self, key, gen: int) -> None:
        """Streaming rollout -> device-ring ingest; only scalar counters
        reach the host, reported to the server loop for the books.

        Under plane: split the rollout dispatch holds only the ACTOR
        mesh's locks — it overlaps the learner plane's train dispatches —
        and the record batch crosses to the learner mesh before ingest
        (which shares the learner locks with training, preserving the
        ring donation contract per plane).

        Split/fused and the meshes are resolved at ENTRY, so a watchdog
        restart after a split -> fused degradation re-enters here and
        picks up the learner-mesh plumbing."""
        import jax

        from ..parallel.mesh import dispatch_serialized

        split = self._param_cache is not None
        roll_mesh = (
            self._actor_mesh if split else self._data_mesh
        )
        # entry-captured refs: a concurrent watchdog degrade nulls the
        # attributes, and a late-waking superseded thread must die at its
        # liveness check, not on a None deref mid-iteration
        record_xfer = self._record_xfer
        plane_stats = self._plane_stats
        key, k0 = jax.random.split(key)
        vstate = self._venv.init(self._device_games, k0)
        hidden = self.module.initial_state(
            (self._device_games, self._venv.num_players)
        )
        if roll_mesh is not None:
            # commit every dispatch input onto the rollout mesh UP FRONT:
            # the loop's args then match the program's pinned in_shardings
            # exactly, so no dispatch triggers an implicit host->mesh
            # reshard.  That implicit copy is not just a per-dispatch
            # transfer on the hot path — under plane: split it races the
            # async ingest running on the OTHER plane's devices (observed
            # on the multi-process CPU backend as Execute() placement
            # errors killing the rollout thread), and committed args keep
            # every cross-device move explicit and plane-owned.  The key
            # stays mesh-resident too: split() of a committed key runs on
            # the actor mesh and its outputs inherit the placement.
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(roll_mesh, PartitionSpec())
            lanes = NamedSharding(roll_mesh, PartitionSpec("dp"))
            key = jax.device_put(key, rep)
            vstate = jax.device_put(vstate, lanes)
            if hidden is not None:
                hidden = jax.device_put(hidden, lanes)
        from collections import deque

        pending_steps = 0   # game steps from batches that finished 0 episodes
        dispatches = 0
        # model epoch per in-flight deferred ingest, aligned with
        # DeviceReplay's stats FIFO: the stats that come back are one
        # dispatch old, and booking them under the CURRENT epoch would
        # misattribute one k_steps block's generation stats at every
        # model publish
        epoch_fifo: deque = deque()
        try:
            while self._rollout_live(gen):
                if self.num_returned_episodes >= self._next_update_episodes:
                    with trace_span("rollout.budget_wait"):
                        time.sleep(0.02)   # epoch episode budget met: yield the chip
                    self._rollout_beat()  # backpressure idle is healthy
                    if split:
                        plane_stats.bump(actor_idle_s=0.02)
                    continue
                if self._maybe_wedge(gen, dispatches):
                    return
                epoch, params = self._actor_params()
                t_busy = time.perf_counter()
                key, sub = jax.random.split(key)
                with trace_span("rollout.dispatch", epoch=epoch):
                    vstate, hidden, records = dispatch_serialized(
                        lambda: self._stream_fn(params, vstate, hidden, sub),
                        roll_mesh,
                    )
                if split:
                    records = record_xfer(records)
                # deferred stats (the direct-ingest hot path): the records
                # go straight into the learner-mesh rings and the scalar
                # fetch for dispatch N happens only after N+1 is enqueued —
                # the rollout thread never synchronizes on an ingest.  The
                # returned stats are therefore ONE DISPATCH OLD (None on
                # the first), which only lags the books by one k_steps
                # block — their model epoch rides epoch_fifo so the
                # generation-stats attribution stays exact; the tail is
                # flushed in the finally below.
                epoch_fifo.append(epoch)
                with trace_span("rollout.ingest", epoch=epoch):
                    stats = self._replay.ingest_counted(records, defer=True)
                dispatches += 1
                self._rollout_dispatched = True  # arms stall detection
                self._rollout_beat()
                if split:
                    plane_stats.bump(
                        actor_busy_s=time.perf_counter() - t_busy
                    )
                if not self._rollout_live(gen):
                    return
                if stats is None:
                    continue
                stats_epoch = epoch_fifo.popleft()  # the dispatch they're from
                n = int(stats["episodes"])
                pending_steps += int(stats["game_steps"])
                if n == 0:
                    continue   # steps stay in pending_steps for the next report
                counts = {
                    "episodes": n,
                    "players": self._venv.num_players,
                    "model_id": stats_epoch,
                    "game_steps": pending_steps,
                    # graftlint: allow[HS001] reason=stats are host numpy from the deferred ingest fetch (one dispatch old), not device values
                    "outcome_sum": float(stats["outcome_sum"].sum()),
                    # graftlint: allow[HS001] reason=stats are host numpy from the deferred ingest fetch (one dispatch old), not device values
                    "outcome_sq_sum": float(stats["outcome_sq_sum"]),
                }
                pending_steps = 0
                if not self._submit_counts(counts, gen):
                    return
        finally:
            # settle the deferred tail so its episodes still reach the
            # books — but only while the run is live (a watchdog restart):
            # a shutdown-time submission could push num_returned_episodes
            # over the next boundary and conjure a spurious extra epoch
            # out of the drain (pre-deferral behavior dropped the tail)
            try:
                left = self._replay.flush_counted()
            except Exception:
                left = None
            if self.shutdown_flag:
                left = None
            if left and (int(left["episodes"]) > 0 or pending_steps):
                counts = {
                    "episodes": int(left["episodes"]),
                    "players": self._venv.num_players,
                    # oldest in-flight dispatch's epoch, not the current
                    # model_epoch: a restart racing a model publish would
                    # otherwise book the tail under a model that never
                    # generated it (the tail can span several epochs; the
                    # oldest is the closest single attribution)
                    "model_id": int(epoch_fifo[0]) if epoch_fifo else self.model_epoch,
                    "game_steps": pending_steps + int(left["game_steps"]),
                    "outcome_sum": float(left["outcome_sum"]),
                    "outcome_sq_sum": float(left["outcome_sq_sum"]),
                }
                # same submission protocol as the loop body (patience while
                # this generation is live; a superseded/stopping thread
                # gives up instead of blocking teardown)
                self._submit_counts(counts, gen)

    def _submit_counts(self, counts: Dict[str, Any], gen: int) -> bool:
        """Report ingest counters to the server loop with the same patience
        loop as _device_rollout_inner (the server can be busy for minutes
        at an epoch boundary).  False = stop the rollout loop."""
        fut: Future = Future()
        # the server loop serves no request while it runs an epoch boundary
        # (update(): snapshot wait, checkpoint, eval), so the first submit
        # after the one that closed the epoch stands here until it is over
        with trace_span("rollout.submit"):
            self._requests.put(("device_counts", counts, fut))
            while not fut.done():
                try:
                    fut.result(timeout=5.0)
                    self._rollout_beat()  # served: the wait was the server's
                except (TimeoutError, FutureTimeoutError):
                    self._rollout_beat()  # waiting on a busy server ≠ a stall
                    if not self._rollout_live(gen):
                        return False
                except Exception:
                    return False
        return True

    def _device_rollout_inner(self, roll, key, gen: int) -> None:
        import jax

        roll_mesh = getattr(roll, "mesh", None)
        if roll_mesh is not None:
            # mesh-resident key, same contract as _device_replay_inner:
            # dispatch args never ride an implicit host->mesh reshard
            from jax.sharding import NamedSharding, PartitionSpec

            key = jax.device_put(key, NamedSharding(roll_mesh, PartitionSpec()))
        dispatches = 0
        while self._rollout_live(gen):
            if self.num_returned_episodes >= self._next_update_episodes:
                time.sleep(0.02)
                self._rollout_beat()  # backpressure idle is healthy
                if self._plane_stats is not None:
                    self._plane_stats.bump(actor_idle_s=0.02)
                continue
            if self._maybe_wedge(gen, dispatches):
                return
            epoch, params = self._actor_params()
            t_busy = time.perf_counter()
            key, sub = jax.random.split(key)
            episodes = roll.generate(params, sub)
            dispatches += 1
            self._rollout_dispatched = True  # arms stall detection
            self._rollout_beat()
            if self._plane_stats is not None:
                self._plane_stats.bump(actor_busy_s=time.perf_counter() - t_busy)
            for ep in episodes:
                ep["args"]["model_id"] = {p: epoch for p in ep["players"]}
            if not self._rollout_live(gen):
                return
            # submit once and wait on the SAME future with a patience loop:
            # the server loop can be busy for minutes at an epoch boundary
            # (trainer snapshot + first-epoch jit compile), and re-raising
            # on a fixed timeout would silently kill on-device generation
            # for the rest of the run
            fut: Future = Future()
            self._requests.put(("device_episodes", episodes, fut))
            while not fut.done():
                try:
                    fut.result(timeout=5.0)
                    self._rollout_beat()
                except (TimeoutError, FutureTimeoutError):
                    self._rollout_beat()  # waiting on a busy server ≠ a stall
                    if not self._rollout_live(gen):
                        return  # server draining/exited; nothing to feed
                except Exception:
                    return

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
            if self._plane_gateway is not None:
                self._plane_gateway.start()
            self._trainer_thread = threading.Thread(
                target=self.trainer.run, daemon=True, name="trainer"
            )
            self._trainer_thread.start()
            self.worker.run()
            self._active_workers = len(getattr(self.worker, "threads", [])) or self.args["worker"]["num_parallel"]
            if self._device_games > 0:
                self._start_rollout_thread()
                threading.Thread(
                    target=self._watchdog_loop, daemon=True, name="plane-watchdog"
                ).start()
            self._start_flywheel_ingest()
            self.server()
            if self._plane_gateway is not None:
                # run concluding: answer every further actor-host request
                # with a clean stop (they exit 0, not as counted losses)
                self._plane_gateway.begin_stop()
            if self._rollout_thread is not None:
                # let an in-flight device call drain: tearing down the
                # interpreter while a daemon thread is inside an XLA execute
                # aborts the process (C++ exception at exit).  Under a drain
                # the join is bounded by the remaining deadline.
                timeout = 120.0
                if self._drain_requested:
                    left = self.drain_deadline - (time.time() - self._drain_t0)
                    timeout = max(5.0, min(120.0, left))
                self._rollout_thread.join(timeout=timeout)
        finally:
            if self._flywheel_ingestor is not None:
                self._flywheel_ingestor.stop()
            if self._health is not None:
                self._health.stop()
            if self._collective_watchdog is not None:
                self._collective_watchdog.stop()
            if self._plane_gateway is not None:
                self._plane_gateway.stop()
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


def _finish_distributed(learner: "Learner") -> None:
    from ..parallel.distributed import shutdown_distributed

    if learner.shutdown_coherent:
        shutdown_distributed()


def train_main(args: Dict[str, Any]) -> None:
    learner = Learner(args)
    code = learner.run()
    _finish_distributed(learner)
    if code:
        sys.exit(code)


def train_server_main(args: Dict[str, Any]) -> None:
    learner = Learner(args, remote=True)
    code = learner.run()
    _finish_distributed(learner)
    if code:
        sys.exit(code)
