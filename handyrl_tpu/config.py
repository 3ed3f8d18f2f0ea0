"""Configuration loading and validation.

Keeps the reference's config.yaml schema (env_args / train_args /
worker_args, reference config.yaml:2-38, docs/parameters.md) so existing
configs port unchanged, and layers defaults + validation on top (the
reference has no validation layer).  TPU-specific knobs live under
``train_args`` with safe defaults:

* ``mesh``: axis-name -> size dict for the device mesh ({'dp': -1} means
  "all devices data-parallel").
* ``inference_batch_size``: max cross-environment batch for the actor-side
  TPU inference engine.
* ``num_actors`` alias: ``worker.num_parallel``.
"""

from __future__ import annotations

import copy
from typing import Any, Dict

import yaml

DEFAULT_TRAIN_ARGS: Dict[str, Any] = {
    "turn_based_training": True,
    "observation": False,
    "gamma": 0.8,
    "forward_steps": 16,
    "burn_in_steps": 0,
    "compress_steps": 4,
    "entropy_regularization": 1.0e-1,
    "entropy_regularization_decay": 0.1,
    "update_episodes": 200,
    "batch_size": 128,
    "minimum_episodes": 400,
    "maximum_episodes": 100000,
    "epochs": -1,
    "num_batchers": 2,
    "eval_rate": 0.1,
    "worker": {
        "num_parallel": 6,
        "entry_port": 9999,
        "data_port": 9998,
        # liveness ping cadence on the remote actor plane, both directions
        # (server -> gathers from a dedicated thread, gathers -> server);
        # a peer silent for ~3 intervals is presumed dead.  0 disables
        # heartbeats AND the silence deadline (pre-fault-tolerance wire
        # behavior, for debugging only)
        "heartbeat_interval": 10.0,
        # max stall (no byte of progress) on gather RPC send/receive: a
        # WAN blackhole surfaces as TimeoutError -> teardown -> rejoin,
        # never a hang, while a big params blob trickling over a slow
        # link stays alive as long as bytes flow
        "socket_timeout": 60.0,
        # entry-handshake deadline: a client that connects and stalls is
        # dropped so the single entry thread keeps serving later joins
        "entry_timeout": 10.0,
    },
    "lambda": 0.7,
    "policy_target": "TD",
    "value_target": "TD",
    "eval": {"opponent": ["random"]},
    "seed": 0,
    # 0 = fresh start; N > 0 = resume from models/{N}.ckpt (digest-checked
    # against models/MANIFEST.json, refusing corrupt files); -1 = AUTO:
    # resume from the newest manifest entry that verifies, falling back to
    # older verified snapshots — the knob a preemptible-TPU launcher sets
    # once and never touches again
    "restart_epoch": 0,
    # epoch snapshots ({N}.ckpt) kept on disk; older ones are GC'd at each
    # save (latest.ckpt / state.ckpt always survive).  0 = keep all
    "keep_checkpoints": 100,
    # shm batcher supervision (runtime/shm_batch.py): respawn a dead
    # batcher child up to this many times, then degrade loudly to the
    # threaded pipeline; also degrade if the ring moves nothing for
    # batcher_stall_timeout seconds after a death (a SIGKILL can take a
    # multiprocessing queue lock with it)
    "batcher_max_restarts": 3,
    "batcher_stall_timeout": 60.0,
    # --- self-healing run plane (docs/fault_tolerance.md) ---------------
    # divergence sentinel: finite-checks of loss/grad-norm are fused into
    # the compiled train step; a bad step's update is SKIPPED (never
    # applied), and sentinel_rollback_after consecutive bad steps (in-step
    # nonfinite flags + host-side loss-spike EMA detections) roll the train
    # state back to the newest VERIFIED manifest checkpoint with re-seeded
    # sampling RNG.  false = bit-identical pre-sentinel step
    "sentinel": True,
    "sentinel_rollback_after": 8,
    # host EMA spike detector: a step whose |loss|/datum exceeds
    # sentinel_spike_factor x the EMA counts as bad (PaLM-style loss-spike
    # handling); the EMA ignores bad steps so divergence can't drag it up
    "sentinel_spike_factor": 10.0,
    "sentinel_loss_ema_decay": 0.9,
    # plane watchdog (device-rollout runs): a rollout thread that dies or
    # makes no progress for plane_stall_timeout seconds is restarted up to
    # plane_max_restarts times; past the budget a split-plane run degrades
    # split -> fused loudly.  plane_param_lag_bound > 0 additionally treats
    # actor params lagging more than that many updates as a stall (0 = off)
    "plane_stall_timeout": 120.0,
    "plane_max_restarts": 2,
    "plane_param_lag_bound": 0,
    # preemption-safe drain: on SIGTERM/SIGINT the run stops cleanly,
    # writes a final manifest-verified checkpoint within this budget, and
    # exits 75 (EX_TEMPFAIL) so a launcher relaunches with restart_epoch -1
    "drain_deadline_seconds": 60.0,
    # --- TPU-native additions -------------------------------------------
    "mesh": {"dp": -1},
    # multi-host learner plane (parallel/distributed.py): set
    # coordinator_address ("host:port" of process 0) + num_processes (+
    # process_id or PROCESS_ID env) to span hosts with jax.distributed.
    # initialization_timeout bounds startup against a dead/mis-addressed
    # coordinator (loud error, never a hang); the heartbeat/collective
    # knobs drive the cross-host health plane (parallel/health.py): a
    # lost or wedged peer is detected within heartbeat_timeout (or
    # collective_timeout for a silent wedge), the coordinator drain-saves
    # a verified checkpoint, and every survivor exits 75 for a
    # restart_epoch: -1 relaunch instead of hanging in a dead collective
    "distributed": {
        "coordinator_address": None,
        "num_processes": 1,
        "process_id": None,
        "initialization_timeout": 300.0,
        "heartbeat_interval": 5.0,
        "heartbeat_timeout": 30.0,
        "collective_timeout": 300.0,
        # health plane's TCP port on the coordinator host (0 = derive:
        # coordinator port + 1)
        "health_port": 0,
        # pod-slice topology (docs/performance.md §Pod-slice topology):
        # 'learner' processes join the jax.distributed collective and run
        # the cadenced train loop; 'actor' processes stay OUTSIDE the
        # collective (their loss must be degradable, not a collective
        # wedge) and stream rollout records to the learner's plane
        # gateway over DCN, polling versioned params back
        "role": "learner",
        # plane gateway's TCP port on the coordinator host (0 = derive:
        # health port + 1); carries param publishes + record transfers
        # for distributed.role: actor processes
        "plane_port": 0,
        # dedicated actor-host processes expected to connect to the plane
        # gateway (0 = no cross-host actor tier; rung-1 per-process device
        # planes only).  Informational for sizing/metrics — a lost actor
        # host degrades throughput, it never gates the run
        "actor_hosts": 0,
    },
    "inference_batch_size": 64,
    "prefetch_batches": 2,
    # batch-assembly plane: 'shm' (default) forks num_batchers PROCESSES
    # that write columnar batches into shared-memory ring slots — GIL-free,
    # zero-copy on the consumer side (runtime/shm_batch.py); 'device'
    # uploads host-born episodes ONCE into device ring buffers and
    # samples/assembles training windows ON DEVICE (runtime/device_batch.py
    # + DeviceEpisodeStage — make_batch and the per-update observation H2D
    # re-upload leave the hot loop; single-process, ff mode needs
    # turn_based_training: false, turn mode needs observation: true);
    # 'thread' keeps the in-process threaded batchers.  A configured plane
    # that cannot be built or started raises; the only hand-over left is
    # the supervised degrade after batcher deaths (pipe_batcher_fallback)
    "batch_pipeline": "shm",
    # shared-memory ring depth, in slots of one (B, T, P, ...) batch each;
    # clamped up to 2*fused_steps + 2 so the double-buffered device-put can
    # keep two fused groups in flight while the children keep filling
    "shm_slots": 6,
    # batch_pipeline: device geometry — episodes queue over this many ring
    # lanes (rounded up to a mesh-dp multiple), each slots steps deep, and
    # upload in (chunk, lanes) blocks.  Keep lanes*chunk well below
    # minimum_episodes x the typical episode length or the first flush
    # waits on generation
    "device_stage_lanes": 8,
    "device_stage_slots": 1024,
    "device_stage_chunk": 64,
    # k SGD updates fused under one lax.scan per device call (amortizes
    # per-call dispatch for small models); 1 = one jit call per update.
    # Semantics are identical: lr is already held constant within an epoch.
    "fused_steps": 1,
    # N > 0: generate self-play episodes fully ON DEVICE, N parallel games
    # per jit call (envs exposing a vector twin, e.g. TicTacToe). Workers
    # then skew toward evaluation; 0 = host actors only.
    "device_rollout_games": 0,
    # true: keep the self-play data on device end to end — rollout records
    # are ingested into device ring buffers and training batches are
    # sampled + assembled + stepped in one dispatch (runtime/
    # device_replay.py).  Needs device_rollout_games > 0; two window
    # modes picked by turn_based_training (see docs/parameters.md).
    "device_replay": False,
    # N > 0: play N batched net-vs-baseline eval matches ON DEVICE at
    # every epoch boundary (runtime/device_eval.py) — the per-epoch
    # win-rate curve host eval workers starve on slow hosts.  Opponent
    # follows eval.opponent when it is random/rulebase (envs without a
    # rule_based_action_all device twin fall back to random).
    "device_eval_games": 0,
    # device-plane topology: 'fused' (default) runs self-play and training
    # time-sliced on ONE mesh; 'split' partitions the devices into a
    # learner mesh (train_args.mesh over the leading devices) and an actor
    # mesh (the trailing actor_chips devices) so both planes run at full
    # duty CONCURRENTLY — params flow actor-ward every
    # param_refresh_updates learner steps, trajectories learner-ward
    # (runtime/plane.py).  Needs device_rollout_games > 0 and >= 2 devices
    "plane": "fused",
    # devices carved off for the actor plane under plane: split
    "actor_chips": 1,
    # learner steps between cross-mesh param refreshes of the actor plane
    # (plane: split): the actor's params are at most this stale — the
    # plane_param_lag metric surfaces the realized lag
    "param_refresh_updates": 8,
    # ring length in steps per lane for device_replay
    "device_replay_slots": 1024,
    # game steps advanced per rollout dispatch in the device_replay loop
    "device_replay_k_steps": 32,
    # --- inference serving plane (docs/serving.md) ----------------------
    # `main.py --serve` (or ServingServer embedded): continuous-batching
    # inference over the framed-socket transport, multi-model routing and
    # zero-downtime hot-swap on new verified checkpoints
    "serving": {
        # TCP port the serving front listens on (0 = ephemeral, for tests)
        "port": 9997,
        # resident snapshot engines beyond which the LRU non-latest engine
        # is retired (drained, never dropped); the latest is always pinned
        "max_models": 4,
        # default per-request latency budget: a request with no explicit
        # slo_ms must complete within this or be shed/expired (not imposed
        # under shed_policy: none)
        "slo_ms": 200.0,
        # 'deadline' sheds on predicted SLO violation (queue waves x EMA
        # batch time), 'queue' sheds only at queue_bound, 'none' never
        # sheds and imposes no default deadline (every admitted request
        # completes — drain semantics; explicit request slo_ms still holds)
        "shed_policy": "deadline",
        # power-of-two bucket cap per device batch (engine max_batch)
        "max_batch": 64,
        # straggler wait once the first request of a batch arrived
        "max_wait_ms": 2.0,
        # bucket sizes compiled at engine build / before a hot-swap flip;
        # the first post-swap request must never pay an XLA compile
        "warm_buckets": [1, 8],
        # queued-request bound per engine (both shed policies enforce it)
        "queue_bound": 1024,
        # silent-client reaping deadline on the server hub (0 = keep
        # idle connections forever; request/reply clients may be bursty)
        "recv_timeout": 0.0,
        # seconds between checkpoint-manifest polls for auto hot-swap on
        # a new verified snapshot (0 = swap only on explicit request)
        "watch_interval": 0.0,
        # seconds between serve_* health records appended to metrics_path
        # by the standalone server (0 = off)
        "stats_interval": 30.0,
        # server-resident recurrent sessions (docs/serving.md §Fleet tier):
        # device-resident hidden states pinned per open session before the
        # LRU spills to host (0 disables the session cache entirely —
        # open_session frames become bad_request, ship-state still works)
        "session_capacity": 1024,
        # host-side spill ring beyond session_capacity: evicted sessions
        # park here as numpy and re-upload on next touch (counted as
        # session_restored); beyond this the oldest spill is dropped and
        # its next touch is an affinity miss (fresh initial state)
        "session_spill": 4096,
        # engine param residency: 'float32' (exact) or 'int8' (per-channel
        # symmetric weight-only quantization, fp32 scales, dequantize
        # fused into the compiled apply — models/quantize.py).  Applied
        # at engine build, so ModelRouter engines, fleet replicas, and
        # frozen league opponents all inherit it; win-rate parity is
        # MEASURED (tests/test_lowprec.py's slow pit), never assumed
        "weight_dtype": "float32",
        # replay-episode calibration batches sampled at publish when
        # weight_dtype is int8: the router replays stored observations
        # through the fp32 and int8 engines and logs the measured output
        # deviation (0 = skip the calibration record)
        "calibration_batches": 4,
    },
    # --- fleet serving tier (docs/serving.md §Fleet tier) ----------------
    # `main.py --fleet`: a front-end entry port proxying rid-pipelined
    # client frames across N `--serve` (or `--edge`) replicas — balance by
    # polled shed-rate/queue-depth, session affinity to the replica holding
    # the hidden state, loud replica_lost failover + backoff rejoin, and
    # replica-by-replica fleet-wide hot-swap
    "fleet": {
        # TCP entry port the router listens on (0 = ephemeral, for tests)
        "port": 9996,
        # backend replicas: "host:port" strings or {host, port, tags}
        # dicts; tag "edge" marks feed-forward-only artifact capacity
        # (skipped by stateful routes and swap propagation)
        "replicas": [],
        # seconds between stats-frame polls feeding the load scores
        "stats_poll_s": 2.0,
        # transient-fault budget for that poll (utils/retry.py): up to
        # poll_retry_attempts retries with exponential backoff starting
        # at poll_retry_backoff_s before a failing poll may declare the
        # replica lost — one EINTR/ECONNRESET never costs a replica_lost
        "poll_retry_attempts": 3,
        "poll_retry_backoff_s": 0.1,
        # per-replica stall deadline: a replica silent this long with
        # proxied requests pending is declared lost (bounded failover);
        # 0 disables (failover then only on connection loss)
        "replica_stall_s": 30.0,
        # lost-replica rejoin backoff: starts at rejoin_backoff_s, doubles
        # to rejoin_backoff_max_s, retries forever (PR 2 discipline)
        "rejoin_backoff_s": 1.0,
        "rejoin_backoff_max_s": 30.0,
        # seconds between fleet_* health records appended to metrics_path
        # (0 = off)
        "stats_interval": 30.0,
        # planned-retire budget: seal -> drain in-flight -> export the
        # SessionCache -> import on the successor must finish inside this,
        # else the retire proceeds lossy (sessions re-open as counted
        # affinity misses — degraded loudly, never a hang)
        "migrate_timeout_s": 30.0,
        # elastic fleet (docs/serving.md §Elastic fleet): replica count
        # driven by the windowed shed rate / queue depth the balancer
        # already polls.  Spawned replicas join warm-then-admit (never
        # routed to before their engine is published and warmed); retires
        # go through the zero-loss session-migration path
        "autoscale": {
            "enabled": False,
            # replica-count bounds (non-edge replicas; config-registered
            # replicas are the operator's floor — never auto-retired)
            "min_replicas": 1,
            "max_replicas": 4,
            # seconds between autoscale decisions
            "interval_s": 1.0,
            # scale UP when the windowed shed rate exceeds this SLO...
            "shed_slo": 0.01,
            # ...or mean queue depth per replica exceeds depth_high;
            # scale DOWN only once depth falls under depth_low with zero
            # sheds for scale_down_after_s straight (hysteresis)
            "depth_high": 64.0,
            "depth_low": 1.0,
            "scale_down_after_s": 30.0,
            # minimum seconds between any two scale actions
            "cooldown_s": 10.0,
            # a spawned replica that is not warm (admitted) within this
            # is marked lost and cycles through the rejoin backoff
            "warm_timeout_s": 120.0,
        },
        # CPU edge replica (`main.py --edge`): port, request threads, and
        # the frozen artifact it serves (CLI path argument overrides)
        "edge_port": 9995,
        "edge_workers": 2,
        "edge_model": "",
    },
    # --- league training plane (docs/league.md) -------------------------
    # `main.py --league` (handyrl_tpu/league): population-based training —
    # a persistent League of frozen snapshots + anchors backed by the
    # checkpoint manifest, PFSP matchmaking over a per-ordered-pair payoff
    # ledger, ModelRouter-resident opponent engines, and a gated promotion
    # that freezes the candidate into the population
    "league": {
        # opponent sampling over the frozen population (AlphaStar PFSP):
        # 'var' weights p(1-p) (focus near-peers), 'hard' weights (1-p)^2
        # (focus the hardest), 'even' is uniform; p = candidate win rate
        "pfsp_weighting": "var",
        # fraction of league generation matches played latest-vs-latest
        # (pure self-play keeps the candidate from overfitting the pool)
        "selfplay_rate": 0.2,
        # promotion gate: the candidate freezes into the population only
        # once every active opponent has >= promote_games recorded games
        # AND the candidate's aggregate win points across the pool reach
        # promote_winrate (win points = wins + draws/2, wp_func convention)
        "promote_winrate": 0.55,
        "promote_games": 8,
        # frozen members kept active for matchmaking (oldest non-anchor
        # members retire from the pool first; their snapshots and payoff
        # books persist).  The anchor always stays active
        "max_population": 16,
    },
    # --- data flywheel (docs/serving.md §Data flywheel) ------------------
    # quality-guarded production loop: the serving tier assembles served
    # traffic into complete training episodes (harvest), the learner
    # pulls them into its EpisodeStore alongside/instead of self-play,
    # and promotions of new snapshots into serving are gated on LIVE win
    # rate with an auto-rollback quality sentinel behind the gate
    "flywheel": {
        "enabled": False,
        # fraction of each epoch's update_episodes budget filled from
        # harvested traffic (the rest stays self-play); 1.0 = train on
        # served traffic only, 0.0 = quality plane without harvest ingest
        "harvest_fraction": 0.5,
        # drop harvested episodes generated >= this many model epochs
        # behind the learner's current epoch (staleness bound)
        "staleness_epochs": 4,
        # where the learner's ingest loop dials the serving tier; port 0
        # follows serving.port
        "harvest_host": "127.0.0.1",
        "harvest_port": 0,
        # ingest poll cadence / per-poll episode cap
        "harvest_poll_s": 1.0,
        "harvest_max_pull": 64,
        # server-side harvest hygiene: an open episode idle past the TTL
        # is dropped (counted truncated); at most max_open concurrent
        # open episodes (the oldest sheds first)
        "harvest_ttl_s": 600.0,
        "harvest_max_open": 256,
        # promotion gate: a fresh snapshot is staged as a shadow
        # candidate on shadow_fraction of default-route traffic and the
        # served `latest` flips only once its live win points over
        # promote_games reported games clear promote_winrate; gating off
        # = every fresh snapshot flips immediately (the PR 13 behavior)
        "gate_promotions": True,
        "promote_winrate": 0.55,
        "promote_games": 16,
        "shadow_fraction": 0.25,
        # quality sentinel behind the gate: a PROMOTED snapshot whose
        # live win-point EMA (window quality_window games) degrades more
        # than demote_drop below the incumbent's bar is demoted
        # serving-side and a verified rollback signal reaches training
        "quality_window": 32,
        "demote_drop": 0.15,
    },
    # --- observability plane (docs/observability.md) --------------------
    # structured span tracing (utils/trace.py): ring-buffered in-process
    # spans over the hot-path seams (dispatch, batch waits, cadence
    # broadcasts, heartbeats, serving lifecycle, epoch-boundary work),
    # flushed to trace.jsonl with the metrics.jsonl tail discipline and
    # exportable to chrome://tracing via scripts/trace_export.py.  OFF by
    # default and provably free: with enabled: false the hot path is
    # bit-identical (one attribute check per seam) — pinned by the obs
    # sanitizer suite
    "trace": {
        "enabled": False,
        # sink path; multi-process ranks N > 0 derive path.rankN.jsonl
        "path": "trace.jsonl",
        # bounded in-process span ring: a full ring DROPS (counted in the
        # trace_dropped metric), never blocks a dispatch
        "ring_size": 4096,
        # background flusher cadence, seconds
        "flush_interval": 0.5,
        # also enter a jax.profiler.TraceAnnotation per span so host spans
        # land inside XLA device profiles (profile_dir captures)
        "annotate_device": True,
    },
    "observability": {
        # multi-process runs: followers piggyback per-epoch metric
        # snapshots on health-plane heartbeats so the coordinator's
        # metrics.jsonl carries rank_* aggregates for EVERY rank (a
        # wedged-but-heartbeating follower is visible as a stale rank
        # report before the collective watchdog's bound)
        "rank_metrics": True,
    },
    # N > 0: when an env's vector twin is autovec-lifted (envs/autovec.py
    # __autovec__), play N random step-parity games between the numpy
    # rules and the lifted device env at Learner startup and refuse to
    # train on a divergent lift.  0 = trust the lift (the parity suite
    # covers bundled rules)
    "autovec_verify_games": 0,
    "metrics_path": "metrics.jsonl",
    "model_dir": "models",
    "battle_port": 9876,
    "profile_dir": None,
    # whole-window attention training for transformer models (models that
    # set supports_seq); turn off to force the step-scan path
    "seq_forward": True,
    # seq-mode attention implementation ('attn_mode' is an accepted
    # alias): 'auto' (Pallas masked flash attention when the window is
    # >= flash_min_t, einsum shorter — on TPU compiled, on CPU via the
    # exact Pallas interpreter; other backends fall back to einsum),
    # 'flash', 'einsum', or 'ring' (sequence-parallel masked ring
    # attention — needs an 'sp' mesh axis)
    "seq_attention": "auto",
    # auto-mode crossover: windows shorter than this use the exact einsum
    # path (the O(T^2) term is tiny and XLA-fusable at short T; the
    # Pallas kernel pays fixed launch/block overhead)
    "flash_min_t": 128,
    # flash kernel tile sizes (query/key rows per VMEM block): power-of-two
    # multiples of 8, clamped to the 128-lane tile inside the kernel.  128
    # is the measured sweet spot; smaller tiles trade MXU utilization for
    # less VMEM per program
    "blk_q": 128,
    "blk_k": 128,
    # recompute ladder for the transformer seq path: 'none' (store every
    # activation), 'attn' (recompute each attention sublayer in the
    # backward), 'block' (recompute whole attention+FFN blocks — the lever
    # that fits T1024 x d1536 in HBM), or 'auto' ('block' for T >= 512 on
    # TPU, else 'none').  For RNN scan training the ladder collapses to
    # on/off over the scan body (the historical remat: auto|true|false)
    "remat": "auto",
    # feed-forward models with burn_in_steps 0 slice the training
    # observation to the live prefix of the T axis — numerically identical,
    # skips compute on end-of-episode padding; disable when debugging
    # shape/recompile issues (parallel/train_step.py _ff_compact)
    "compact_padding": True,
    # fully unroll the RNN training scan over T: 'auto' = on for
    # single-device CPU (XLA:CPU runs while-loop bodies without its fast
    # kernel runtime), off for TPU and multi-device meshes (unrolled
    # bodies explode SPMD-partitioner compile time)
    "unroll": "auto",
    # 'bfloat16' runs the forward/backward compute in bf16 (MXU rate)
    # with fp32 master weights; 'float32' is exact
    "compute_dtype": "float32",
    # quantize observation planes to int8 at episode finalize: the actor
    # wire blocks, shm ring slots, and device replay rings then carry
    # int8 obs (4x fewer bytes) and dequantize on device inside the
    # compiled sample/train programs.  Static per-plane scale/zero-point
    # come from env metadata (env.obs_int8_spec(); default scale 1.0 /
    # zero-point 0 — EXACT for 0/1-occupancy planes, which is every
    # bundled env).  models/quantize.py
    "obs_int8": False,
    # multiplies the reference lr schedule (3e-8 x data-count EMA,
    # train.py:328-332) -- 1.0 is exact parity.  The schedule assumes
    # GPU-scale update counts; raise it when the update budget is small
    # (e.g. CI soaks on a slow host).
    "lr_scale": 1.0,
}

DEFAULT_WORKER_ARGS: Dict[str, Any] = {
    "server_address": "",
    "num_parallel": 8,
    "entry_port": 9999,
    # on a severed/stalled connection the worker machine tears its session
    # down (no actor thread survives) and re-enters through the entry port
    # with exponential backoff; rejoin: false restores join-once behavior
    "rejoin": True,
    "rejoin_backoff": 1.0,
    "rejoin_backoff_max": 60.0,
    # bound on consecutive failed sessions before giving up (-1 = forever,
    # the right default for a fleet behind a supervisor)
    "max_rejoins": -1,
    # how long each entry attempt keeps retrying the TCP connect (server
    # still booting / restarting) before counting as a failed session
    "entry_retry_seconds": 60.0,
}

VALID_TARGETS = ("MC", "TD", "UPGO", "VTRACE")


def effective_shm_slots(train: Dict[str, Any]) -> int:
    """The ring depth the shm batch plane ACTUALLY allocates: ``shm_slots``
    clamped up so the double-buffered device-put can keep two fused groups
    in flight while the children keep filling.  Single source of truth —
    ``validate_args`` checks ``num_batchers`` against it and
    ``ShmBatchPipeline`` allocates exactly it; change the consumer's
    buffering depth in one place only."""
    return max(
        int(train.get("shm_slots", 6)),
        2 * int(train.get("fused_steps", 1)) + 2,
        3,
    )


def _deep_merge(base: Dict[str, Any], override: Dict[str, Any]) -> Dict[str, Any]:
    out = copy.deepcopy(base)
    for key, value in (override or {}).items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = _deep_merge(out[key], value)
        else:
            out[key] = value
    return out


def validate_args(args: Dict[str, Any]) -> Dict[str, Any]:
    train = args["train_args"]
    for key in ("policy_target", "value_target"):
        if train[key] not in VALID_TARGETS:
            raise ValueError(f"{key}={train[key]!r} not one of {VALID_TARGETS}")
    for key in ("forward_steps", "batch_size", "update_episodes", "compress_steps"):
        if train[key] <= 0:
            raise ValueError(f"train_args.{key} must be positive, got {train[key]}")
    if train["burn_in_steps"] < 0:
        raise ValueError("train_args.burn_in_steps must be >= 0")
    if train["restart_epoch"] < -1:
        raise ValueError(
            "train_args.restart_epoch must be >= -1 (-1 = auto-resume from "
            "the newest verified snapshot)"
        )
    if train["keep_checkpoints"] < 0:
        raise ValueError("train_args.keep_checkpoints must be >= 0 (0 = keep all)")
    if train["batcher_max_restarts"] < 0:
        raise ValueError("train_args.batcher_max_restarts must be >= 0")
    if train["batcher_stall_timeout"] <= 0:
        raise ValueError("train_args.batcher_stall_timeout must be > 0")
    if train["sentinel_rollback_after"] < 1:
        raise ValueError("train_args.sentinel_rollback_after must be >= 1")
    if train["sentinel_spike_factor"] <= 1.0:
        raise ValueError(
            "train_args.sentinel_spike_factor must be > 1 (a spike is a "
            "multiple of the loss EMA)"
        )
    if not 0.0 < train["sentinel_loss_ema_decay"] < 1.0:
        raise ValueError("train_args.sentinel_loss_ema_decay must be in (0, 1)")
    if train["plane_stall_timeout"] <= 0:
        raise ValueError("train_args.plane_stall_timeout must be > 0")
    if train["plane_max_restarts"] < 0:
        raise ValueError("train_args.plane_max_restarts must be >= 0")
    if train["plane_param_lag_bound"] < 0:
        raise ValueError("train_args.plane_param_lag_bound must be >= 0 (0 = off)")
    if train["drain_deadline_seconds"] <= 0:
        raise ValueError("train_args.drain_deadline_seconds must be > 0")
    dist = train["distributed"]
    if dist["coordinator_address"] is not None:
        # both the init pre-flight (parallel/distributed.py) and the health
        # plane (parallel/health.py) parse host:port out of this — a
        # missing port must fail HERE with a named knob, not as a bare
        # int() traceback inside a socket helper
        _host, _, _port = str(dist["coordinator_address"]).rpartition(":")
        if not _host or not _port.isdigit() or not 1 <= int(_port) <= 65535:
            raise ValueError(
                f"train_args.distributed.coordinator_address="
                f"{dist['coordinator_address']!r} must be 'host:port' with a "
                "TCP port (the address of process 0)"
            )
    if int(dist["num_processes"]) < 1:
        raise ValueError("train_args.distributed.num_processes must be >= 1")
    if dist["process_id"] is not None and int(dist["process_id"]) < 0:
        raise ValueError("train_args.distributed.process_id must be >= 0")
    if float(dist["initialization_timeout"]) <= 0:
        raise ValueError(
            "train_args.distributed.initialization_timeout must be > 0 "
            "(it bounds jax.distributed.initialize against a dead or "
            "mis-addressed coordinator — 0 would restore the indefinite "
            "startup hang)"
        )
    if float(dist["heartbeat_interval"]) < 0:
        raise ValueError(
            "train_args.distributed.heartbeat_interval must be >= 0 "
            "(0 disables the cross-host health plane)"
        )
    if float(dist["heartbeat_timeout"]) <= 0:
        raise ValueError("train_args.distributed.heartbeat_timeout must be > 0")
    if (
        float(dist["heartbeat_interval"]) > 0
        and float(dist["heartbeat_timeout"]) <= 2 * float(dist["heartbeat_interval"])
    ):
        raise ValueError(
            "train_args.distributed.heartbeat_timeout must exceed 2x "
            "heartbeat_interval — a single delayed beat must not count a "
            "live host as lost"
        )
    if float(dist["collective_timeout"]) < 0:
        raise ValueError(
            "train_args.distributed.collective_timeout must be >= 0 "
            "(0 disables the collective watchdog)"
        )
    if not isinstance(dist["health_port"], int) or not 0 <= dist["health_port"] <= 65535:
        raise ValueError(
            f"train_args.distributed.health_port={dist['health_port']!r} "
            "must be a TCP port (0 = coordinator port + 1)"
        )
    if (
        dist["health_port"] == 0
        and dist["coordinator_address"] is not None
        and float(dist["heartbeat_interval"]) > 0  # plane enabled at all
        and int(str(dist["coordinator_address"]).rpartition(":")[2]) >= 65535
    ):
        raise ValueError(
            "train_args.distributed.health_port derives as coordinator "
            "port + 1 = 65536, which is not a TCP port — set "
            "distributed.health_port explicitly"
        )
    # pod-slice topology knobs (docs/performance.md §Pod-slice topology).
    # The device data plane IS supported multi-process now (per-process
    # rings/rollout feed the collective train step through the
    # make_array_from_process_local_data seam, every device dispatch
    # gated on the coordinator cadence, RNGs rank-decorrelated) — so the
    # old blanket rejections became the composition checks below: what
    # must actually hold is that the per-process SHARDS divide evenly
    if str(dist["role"]) not in ("learner", "actor"):
        raise ValueError(
            f"train_args.distributed.role={dist['role']!r} not one of "
            "('learner', 'actor') — learners join the jax.distributed "
            "collective; actor hosts stream records to the plane gateway"
        )
    if not isinstance(dist["plane_port"], int) or not 0 <= dist["plane_port"] <= 65535:
        raise ValueError(
            f"train_args.distributed.plane_port={dist['plane_port']!r} "
            "must be a TCP port (0 = health port + 1)"
        )
    if int(dist["actor_hosts"]) < 0:
        raise ValueError("train_args.distributed.actor_hosts must be >= 0")
    if (int(dist["actor_hosts"]) > 0 or str(dist["role"]) == "actor") and not dist[
        "coordinator_address"
    ]:
        raise ValueError(
            "train_args.distributed.actor_hosts/role: actor need "
            "distributed.coordinator_address — the plane gateway binds on "
            "(and actor hosts dial) the coordinator host"
        )
    if str(dist["role"]) == "actor" and train["device_rollout_games"] <= 0:
        raise ValueError(
            "train_args.distributed.role: actor needs device_rollout_games "
            "> 0 — a dedicated actor host generates with the on-device "
            "streaming rollout (host self-play already has the worker tier)"
        )
    if (
        dist["plane_port"] == 0
        and dist["coordinator_address"] is not None
        and (int(dist["actor_hosts"]) > 0 or str(dist["role"]) == "actor")
        and (
            dist["health_port"]
            or int(str(dist["coordinator_address"]).rpartition(":")[2]) + 1
        )
        >= 65535
    ):
        raise ValueError(
            "train_args.distributed.plane_port derives as health port + 1 "
            "= 65536, which is not a TCP port — set "
            "distributed.plane_port explicitly"
        )
    # the distributed plane only ACTIVATES with a coordinator_address
    # (init_distributed returns 0 without one — num_processes alone may
    # just be a fleet template), so the shard-divisibility checks key
    # on both
    if int(dist["num_processes"]) > 1 and dist["coordinator_address"]:
        nprocs = int(dist["num_processes"])
        if int(train["batch_size"]) % nprocs != 0:
            raise ValueError(
                f"train_args.batch_size={train['batch_size']} must divide "
                f"evenly across distributed.num_processes={nprocs} — each "
                "process assembles batch_size/num_processes local rows for "
                "the collective train step"
            )
        if train["device_rollout_games"] > 0 and (
            int(train["device_rollout_games"]) % nprocs != 0
        ):
            raise ValueError(
                f"train_args.device_rollout_games="
                f"{train['device_rollout_games']} must divide evenly across "
                f"distributed.num_processes={nprocs} — each process runs "
                "device_rollout_games/num_processes lanes on its local "
                "actor devices (the per-mesh lane divisibility is checked "
                "at Learner startup where the local device count is known)"
            )
    if train["worker"]["heartbeat_interval"] < 0:
        raise ValueError("train_args.worker.heartbeat_interval must be >= 0 (0 = off)")
    for key in ("socket_timeout", "entry_timeout"):
        if train["worker"][key] <= 0:
            raise ValueError(f"train_args.worker.{key} must be > 0")
    if train["fused_steps"] < 1:
        raise ValueError("train_args.fused_steps must be >= 1")
    if train["batch_pipeline"] not in ("shm", "thread", "device"):
        raise ValueError(
            f"train_args.batch_pipeline={train['batch_pipeline']!r} "
            "not one of ('shm', 'thread', 'device')"
        )
    if int(train["shm_slots"]) < 2:
        raise ValueError("train_args.shm_slots must be >= 2")
    if int(train["num_batchers"]) < 0:
        raise ValueError(
            "train_args.num_batchers must be >= 0 (0 = in-process threaded "
            "batchers; the shm plane needs at least 1 process)"
        )
    # the ring depth the shm plane is GUARANTEED to allocate on every
    # platform: the runtime may clamp fused_steps down to 1 (multi-device
    # CPU meshes execute fused scans pathologically — trainer.py), which
    # shrinks the 2*fused+2 enlargement with it, so only the fused=1 floor
    # can be promised at config time
    floor_slots = effective_shm_slots(dict(train, fused_steps=1))
    if (
        train["batch_pipeline"] == "shm"
        and int(train["num_batchers"]) > floor_slots
    ):
        # a child beyond the ring depth would never be dealt a slot: it
        # spins forever contributing nothing — fail loudly at startup
        # instead of deep inside shm_batch setup (same spirit as the
        # plane: split validations)
        raise ValueError(
            f"train_args.num_batchers={train['num_batchers']} exceeds the "
            f"guaranteed shm ring depth {floor_slots} (shm_slots="
            f"{train['shm_slots']}; fused_steps can be clamped to 1 at "
            "runtime, so its ring enlargement does not count): each batcher "
            "process needs at least one ring slot to hold — raise shm_slots "
            "or lower num_batchers"
        )
    if train["batch_pipeline"] == "device":
        if train["device_replay"]:
            raise ValueError(
                "train_args.batch_pipeline: device is redundant under "
                "device_replay: true (that path never materializes host "
                "episodes, so there is nothing for the stage to upload)"
            )
        if int(train["device_stage_lanes"]) < 1:
            raise ValueError("train_args.device_stage_lanes must be >= 1")
        if int(train["device_stage_chunk"]) < 1:
            raise ValueError("train_args.device_stage_chunk must be >= 1")
        min_slots = train["burn_in_steps"] + train["forward_steps"]
        if int(train["device_stage_slots"]) <= min_slots:
            raise ValueError(
                "train_args.device_stage_slots must exceed burn_in_steps + "
                f"forward_steps = {min_slots}"
            )
    if train["device_rollout_games"] < 0:
        raise ValueError("train_args.device_rollout_games must be >= 0")
    if train["device_eval_games"] < 0:
        raise ValueError("train_args.device_eval_games must be >= 0")
    if train["device_replay"]:
        if train["device_rollout_games"] <= 0:
            raise ValueError(
                "train_args.device_replay needs device_rollout_games > 0 "
                "(the lane count of the streaming rollout it feeds from)"
            )
        # the remaining constraints (env hooks, feed-forward net, burn-in,
        # turn_based_training) are checked by DeviceReplay at Learner
        # startup, where the env/net are known
        if train["device_replay_slots"] <= train["forward_steps"]:
            raise ValueError("train_args.device_replay_slots must exceed forward_steps")
        if train["device_replay_k_steps"] < 1:
            raise ValueError("train_args.device_replay_k_steps must be >= 1")
    if train["plane"] not in ("fused", "split"):
        raise ValueError(
            f"train_args.plane={train['plane']!r} not one of ('fused', 'split')"
        )
    if int(train["actor_chips"]) < 1:
        raise ValueError("train_args.actor_chips must be >= 1")
    if int(train["param_refresh_updates"]) < 1:
        raise ValueError("train_args.param_refresh_updates must be >= 1")
    if train["plane"] == "split" and train["device_rollout_games"] <= 0:
        raise ValueError(
            "train_args.plane: split needs device_rollout_games > 0 (the "
            "actor plane generates with the on-device streaming rollout; "
            "host actors don't occupy a device plane)"
        )
    # observation: true with device_rollout_games is validated per-env at
    # Learner startup: streaming vector envs with an observe_mask hook
    # (Geister) record observer views; turn-player-only envs must refuse
    if not 0.0 <= train["eval_rate"] <= 1.0:
        raise ValueError("train_args.eval_rate must be in [0, 1]")
    serving = train["serving"]
    if serving["shed_policy"] not in ("deadline", "queue", "none"):
        raise ValueError(
            f"train_args.serving.shed_policy={serving['shed_policy']!r} "
            "not one of ('deadline', 'queue', 'none')"
        )
    if int(serving["max_models"]) < 1:
        raise ValueError("train_args.serving.max_models must be >= 1")
    if float(serving["slo_ms"]) <= 0:
        raise ValueError("train_args.serving.slo_ms must be > 0")
    if int(serving["max_batch"]) < 1:
        raise ValueError("train_args.serving.max_batch must be >= 1")
    if float(serving["max_wait_ms"]) < 0:
        raise ValueError("train_args.serving.max_wait_ms must be >= 0")
    if int(serving["queue_bound"]) < 1:
        raise ValueError("train_args.serving.queue_bound must be >= 1")
    buckets = serving["warm_buckets"]
    if not isinstance(buckets, (list, tuple)) or not buckets:
        raise ValueError(
            "train_args.serving.warm_buckets must be a non-empty list of "
            "bucket sizes"
        )
    for b in buckets:
        if not isinstance(b, int) or b < 1 or (b & (b - 1)):
            raise ValueError(
                f"train_args.serving.warm_buckets entries must be powers of "
                f"two >= 1 (the engine's compiled batch shapes), got {b!r}"
            )
        if b > int(serving["max_batch"]):
            raise ValueError(
                f"train_args.serving.warm_buckets entry {b} exceeds "
                f"serving.max_batch {serving['max_batch']} — it would warm a "
                "shape the engine never dispatches"
            )
    for key in ("recv_timeout", "watch_interval", "stats_interval"):
        if float(serving[key]) < 0:
            raise ValueError(f"train_args.serving.{key} must be >= 0 (0 = off)")
    if not isinstance(serving["port"], int) or not 0 <= serving["port"] <= 65535:
        raise ValueError(
            f"train_args.serving.port={serving['port']!r} must be a TCP port "
            "(0 = ephemeral)"
        )
    for key in ("session_capacity", "session_spill"):
        if int(serving[key]) < 0:
            raise ValueError(
                f"train_args.serving.{key} must be >= 0 "
                "(session_capacity 0 disables the session cache)"
            )
    if serving["weight_dtype"] not in ("float32", "int8"):
        raise ValueError(
            f"train_args.serving.weight_dtype={serving['weight_dtype']!r} "
            "not one of ('float32', 'int8')"
        )
    if int(serving["calibration_batches"]) < 0:
        raise ValueError(
            "train_args.serving.calibration_batches must be >= 0 (0 = skip "
            "the publish-time calibration record)"
        )
    if not isinstance(train["obs_int8"], bool):
        raise ValueError(
            f"train_args.obs_int8={train['obs_int8']!r} must be a bool "
            "(int8 observation planes on the wire/rings)"
        )
    fleet = train["fleet"]
    for key in ("port", "edge_port"):
        if not isinstance(fleet[key], int) or not 0 <= fleet[key] <= 65535:
            raise ValueError(
                f"train_args.fleet.{key}={fleet[key]!r} must be a TCP port "
                "(0 = ephemeral)"
            )
    if not isinstance(fleet["replicas"], (list, tuple)):
        raise ValueError(
            "train_args.fleet.replicas must be a list of 'host:port' strings "
            "or {host, port, tags} dicts"
        )
    for entry in fleet["replicas"]:
        if isinstance(entry, str):
            host, sep, port = entry.rpartition(":")
            if not sep or not port.isdigit():
                raise ValueError(
                    f"train_args.fleet.replicas entry {entry!r} is not "
                    "'host:port'"
                )
        elif isinstance(entry, dict):
            if "host" not in entry or "port" not in entry:
                raise ValueError(
                    f"train_args.fleet.replicas entry {entry!r} needs "
                    "'host' and 'port' keys"
                )
        else:
            raise ValueError(
                f"train_args.fleet.replicas entry {entry!r} must be a "
                "'host:port' string or a dict"
            )
    if int(fleet["poll_retry_attempts"]) < 0:
        raise ValueError(
            "train_args.fleet.poll_retry_attempts must be >= 0 (0 = no "
            "retry, the pre-flywheel fail-at-once behavior)"
        )
    if float(fleet["poll_retry_backoff_s"]) <= 0:
        raise ValueError("train_args.fleet.poll_retry_backoff_s must be > 0")
    if float(fleet["stats_poll_s"]) <= 0:
        raise ValueError(
            "train_args.fleet.stats_poll_s must be > 0 (it feeds the load "
            "scores the router balances by)"
        )
    if float(fleet["replica_stall_s"]) < 0:
        raise ValueError(
            "train_args.fleet.replica_stall_s must be >= 0 (0 disables the "
            "stall deadline; failover then only on connection loss)"
        )
    if float(fleet["rejoin_backoff_s"]) <= 0:
        raise ValueError("train_args.fleet.rejoin_backoff_s must be > 0")
    if float(fleet["rejoin_backoff_max_s"]) < float(fleet["rejoin_backoff_s"]):
        raise ValueError(
            "train_args.fleet.rejoin_backoff_max_s must be >= "
            "rejoin_backoff_s (it is the backoff's cap)"
        )
    if float(fleet["stats_interval"]) < 0:
        raise ValueError("train_args.fleet.stats_interval must be >= 0 (0 = off)")
    if float(fleet["migrate_timeout_s"]) <= 0:
        raise ValueError(
            "train_args.fleet.migrate_timeout_s must be > 0 (the planned-"
            "retire drain/export/import budget)"
        )
    if int(fleet["edge_workers"]) < 1:
        raise ValueError("train_args.fleet.edge_workers must be >= 1")
    autoscale = fleet["autoscale"]
    if not isinstance(autoscale["enabled"], bool):
        raise ValueError(
            f"train_args.fleet.autoscale.enabled={autoscale['enabled']!r} "
            "must be a bool"
        )
    if int(autoscale["min_replicas"]) < 1:
        raise ValueError(
            "train_args.fleet.autoscale.min_replicas must be >= 1 (a fleet "
            "scaled to zero cannot serve)"
        )
    if int(autoscale["max_replicas"]) < int(autoscale["min_replicas"]):
        raise ValueError(
            "train_args.fleet.autoscale.max_replicas must be >= min_replicas"
        )
    for key in ("interval_s", "warm_timeout_s"):
        if float(autoscale[key]) <= 0:
            raise ValueError(f"train_args.fleet.autoscale.{key} must be > 0")
    if not 0.0 <= float(autoscale["shed_slo"]) <= 1.0:
        raise ValueError(
            "train_args.fleet.autoscale.shed_slo must be in [0, 1] (a shed "
            "RATE: sheds over requests in the window)"
        )
    if float(autoscale["depth_low"]) < 0:
        raise ValueError("train_args.fleet.autoscale.depth_low must be >= 0")
    if float(autoscale["depth_high"]) <= float(autoscale["depth_low"]):
        raise ValueError(
            "train_args.fleet.autoscale.depth_high must be > depth_low "
            "(the hysteresis band between scale-up and scale-down)"
        )
    for key in ("scale_down_after_s", "cooldown_s"):
        if float(autoscale[key]) < 0:
            raise ValueError(f"train_args.fleet.autoscale.{key} must be >= 0")
    league = train["league"]
    if league["pfsp_weighting"] not in ("var", "hard", "even"):
        raise ValueError(
            f"train_args.league.pfsp_weighting={league['pfsp_weighting']!r} "
            "not one of ('var', 'hard', 'even')"
        )
    if not 0.0 <= float(league["selfplay_rate"]) <= 1.0:
        raise ValueError("train_args.league.selfplay_rate must be in [0, 1]")
    if not 0.0 < float(league["promote_winrate"]) < 1.0:
        raise ValueError(
            "train_args.league.promote_winrate must be in (0, 1) — it is a "
            "win-points bar over the active population"
        )
    if int(league["promote_games"]) < 1:
        raise ValueError("train_args.league.promote_games must be >= 1")
    if int(league["max_population"]) < 2:
        raise ValueError(
            "train_args.league.max_population must be >= 2 (the anchor "
            "plus at least one frozen member)"
        )
    fly = train["flywheel"]
    if not isinstance(fly["enabled"], bool):
        raise ValueError(
            f"train_args.flywheel.enabled={fly['enabled']!r} must be a bool"
        )
    for key in ("harvest_fraction", "shadow_fraction"):
        if not 0.0 <= float(fly[key]) <= 1.0:
            raise ValueError(f"train_args.flywheel.{key} must be in [0, 1]")
    if not 0.0 < float(fly["promote_winrate"]) < 1.0:
        raise ValueError(
            "train_args.flywheel.promote_winrate must be in (0, 1) — it is "
            "a live win-points bar, not a guarantee"
        )
    if not 0.0 < float(fly["demote_drop"]) < 1.0:
        raise ValueError(
            "train_args.flywheel.demote_drop must be in (0, 1) — the live "
            "win-point EMA drop that trips the quality sentinel"
        )
    if int(fly["staleness_epochs"]) < 1:
        raise ValueError(
            "train_args.flywheel.staleness_epochs must be >= 1 (0 would "
            "drop every harvested episode as stale)"
        )
    for key in ("promote_games", "quality_window", "harvest_max_pull",
                "harvest_max_open"):
        if int(fly[key]) < 1:
            raise ValueError(f"train_args.flywheel.{key} must be >= 1")
    for key in ("harvest_poll_s", "harvest_ttl_s"):
        if float(fly[key]) <= 0:
            raise ValueError(f"train_args.flywheel.{key} must be > 0")
    if not isinstance(fly["gate_promotions"], bool):
        raise ValueError(
            f"train_args.flywheel.gate_promotions="
            f"{fly['gate_promotions']!r} must be a bool"
        )
    if not isinstance(fly["harvest_port"], int) or not (
        0 <= fly["harvest_port"] <= 65535
    ):
        raise ValueError(
            f"train_args.flywheel.harvest_port={fly['harvest_port']!r} must "
            "be a TCP port in [0, 65535] (0 = follow serving.port)"
        )
    if int(train["autovec_verify_games"]) < 0:
        raise ValueError("train_args.autovec_verify_games must be >= 0 (0 = off)")
    tr = train["trace"]
    if not isinstance(tr["enabled"], bool):
        raise ValueError(
            f"train_args.trace.enabled={tr['enabled']!r} must be a bool"
        )
    if tr["enabled"] and not str(tr["path"] or "").strip():
        raise ValueError(
            "train_args.trace.path must name a file when trace.enabled is "
            "true (writability is probed at startup by trace.configure)"
        )
    if int(tr["ring_size"]) < 1:
        raise ValueError("train_args.trace.ring_size must be >= 1")
    if float(tr["flush_interval"]) <= 0:
        raise ValueError("train_args.trace.flush_interval must be > 0")
    if not isinstance(tr["annotate_device"], bool):
        raise ValueError(
            f"train_args.trace.annotate_device={tr['annotate_device']!r} "
            "must be a bool"
        )
    obs = train["observability"]
    if not isinstance(obs["rank_metrics"], bool):
        raise ValueError(
            f"train_args.observability.rank_metrics="
            f"{obs['rank_metrics']!r} must be a bool"
        )
    if train["seq_attention"] not in ("auto", "flash", "einsum", "ring"):
        raise ValueError(
            f"train_args.seq_attention={train['seq_attention']!r} "
            "not one of ('auto', 'flash', 'einsum', 'ring')"
        )
    if int(train["flash_min_t"]) < 1:
        raise ValueError("train_args.flash_min_t must be >= 1")
    for key in ("blk_q", "blk_k"):
        b = int(train[key])
        if b < 8 or (b & (b - 1)):
            raise ValueError(
                f"train_args.{key} must be a power of two >= 8 (8 sublanes x "
                f"the 128-lane tile rule — pallas_guide 'Tiling Constraints'), "
                f"got {train[key]}; the kernel clamps blocks above 128 down "
                "to the lane tile"
            )
    rv = train["remat"]
    # isinstance(bool) first: tuple membership would accept the ints 0/1
    # via ==, which resolve_seq_remat (isinstance-based) would then read
    # as 'auto' — one config value must not mean two things
    if not (isinstance(rv, bool) or rv in ("auto", "none", "attn", "block")):
        raise ValueError(
            f"train_args.remat={rv!r} not one of "
            "('auto', true, false, 'none', 'attn', 'block')"
        )
    uv = train["unroll"]
    if not (isinstance(uv, bool) or uv in ("auto", None)):
        raise ValueError(
            f"train_args.unroll={uv!r} not one of ('auto', true, false)"
        )
    if not isinstance(train["compact_padding"], bool):
        raise ValueError(
            f"train_args.compact_padding={train['compact_padding']!r} "
            "must be a bool"
        )
    mesh = train["mesh"]
    if not isinstance(mesh, dict) or not mesh:
        raise ValueError("train_args.mesh must be a non-empty axis->size dict")
    for ax, size in mesh.items():
        if not isinstance(size, int) or size == 0 or size < -1:
            raise ValueError(
                f"train_args.mesh[{ax!r}]={size!r}: axis sizes are positive "
                "ints or -1 (fill remaining devices)"
            )
    if sum(1 for s in mesh.values() if s == -1) > 1:
        raise ValueError(
            "train_args.mesh: at most one axis may be -1 (fill) — "
            f"got {mesh}"
        )
    if train["seq_attention"] == "ring" and train["remat"] in ("attn", "block", True):
        raise ValueError(
            "train_args.remat ladder is unsupported with seq_attention: "
            "'ring' — the ring already partitions activation memory over "
            "'sp' (each device holds one T/sp shard), and jax.checkpoint "
            "around the shard_map ring loop fails its scan-carry "
            "replication typing; use remat: none or auto"
        )
    if train["seq_attention"] == "ring":
        sp = mesh.get("sp", 1)
        if sp != -1 and sp < 2:
            raise ValueError(
                "train_args.seq_attention: 'ring' needs an 'sp' mesh axis of "
                f"size >= 2 (or -1), got mesh {mesh}"
            )
        T = train["burn_in_steps"] + train["forward_steps"]
        if sp > 0 and T % sp:
            raise ValueError(
                f"train_args.seq_attention: 'ring' window {T} (burn_in_steps "
                f"+ forward_steps) must be divisible by mesh sp={sp}"
            )
    if train["compute_dtype"] not in ("float32", "bfloat16"):
        raise ValueError(
            f"train_args.compute_dtype={train['compute_dtype']!r} "
            "not one of ('float32', 'bfloat16')"
        )
    if train["lr_scale"] <= 0:
        raise ValueError(f"train_args.lr_scale must be > 0, got {train['lr_scale']}")
    worker_args = args.get("worker_args", {})
    if worker_args and float(worker_args.get("entry_retry_seconds", 60.0)) <= 0:
        raise ValueError("worker_args.entry_retry_seconds must be > 0")
    if "env" not in args.get("env_args", {}):
        raise ValueError("env_args.env is required")
    return args


def normalize_args(raw: Dict[str, Any]) -> Dict[str, Any]:
    """Apply defaults to a raw config dict and validate."""
    train_raw = dict(raw.get("train_args", {}) or {})
    # 'attn_mode' is the documented alias for 'seq_attention' (the knob
    # predates the auto-pick policy); an explicit attn_mode wins, and
    # setting both to DIFFERENT values is a config contradiction
    if "attn_mode" in train_raw:
        mode = train_raw.pop("attn_mode")
        if train_raw.get("seq_attention", mode) != mode:
            raise ValueError(
                f"train_args.attn_mode={mode!r} contradicts "
                f"train_args.seq_attention={train_raw['seq_attention']!r} "
                "(attn_mode is an alias; set one)"
            )
        train_raw["seq_attention"] = mode
    args = {
        "env_args": copy.deepcopy(raw.get("env_args", {})),
        "train_args": _deep_merge(DEFAULT_TRAIN_ARGS, train_raw),
        "worker_args": _deep_merge(DEFAULT_WORKER_ARGS, raw.get("worker_args", {})),
    }
    return validate_args(args)


def load_config(path: str = "config.yaml") -> Dict[str, Any]:
    with open(path) as f:
        raw = yaml.safe_load(f) or {}
    return normalize_args(raw)
