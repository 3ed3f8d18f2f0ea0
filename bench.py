"""Headline benchmark suite: real-pipeline throughput on whatever chip is present.

Three measurements, all through the REAL framework paths (no synthetic
kernels):

1. TicTacToe trained env-steps/s — self-play episodes -> replay windows ->
   make_batch -> jitted sharded train step (headline; the reference measured
   39,707 trained env-steps/s on this machine, BASELINE.md).
2. HungryGeese (north-star env) generation throughput — thread actors
   driving the batched cross-env inference engine (the actor-plane TPU
   path); reference single-process generation measured 1,557 env-steps/s.
3. HungryGeese training throughput + input_wait_frac through the threaded
   BatchPipeline, plus MFU from XLA compiled cost analysis (always
   reported — as a number or as null with the reason).
4. The north-star loop itself: streaming on-device HungryGeese self-play
   feeding the store while the learner trains from it concurrently, with
   both planes' rates, learner input starvation, and the per-chip
   fraction of the 100k/v4-32 target.

Every timed window stretches until at least one unit (update / episode)
completes — a slow backend yields a small measured rate or an explicit
null+note, never a silent 0.0.

Prints json lines of the shape {"metric", "value", "unit", "vs_baseline"}
plus "extra": one snapshot after the device lookup and after every stage
(marked "partial") and a final unmarked line, each also atomically
replacing the side file ``bench_snapshot.json`` — so a kill at any moment
leaves the newest parseable state on stdout's last line AND on disk.

No accelerator means a raised error and a non-zero exit: a measurement
path that finds no chip fails, it never falls back to the CPU and writes
CPU rates under the device metric names.  Each stage retries once on a
transient failure; stages that would start with < BENCH_STAGE_MIN_S of
the outer deadline (BENCH_DEADLINE_S, default 1700 s) left are skipped
with an honest note so the run finishes before the driver's kill.
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback
from typing import Optional


REFERENCE_TRAINED_STEPS_PER_SEC = 39707.0  # measured, BASELINE.md (torch CPU)
REFERENCE_GEN_STEPS_PER_SEC = 1557.0       # measured, BASELINE.md (torch CPU, TicTacToe)
# HungryGeese like-for-like: the reference's own loop shape (batch-1 torch
# inference per active player, single process) with the reference's own
# GeeseNet on this host — tools/reference_geese_gen.py.  Rounds 1-3 divided
# the geese stages by the TICTACTOE row above, understating them 17x.
REFERENCE_GEESE_GEN_STEPS_PER_SEC = 89.0   # measured 2026-08-01, BASELINE.md

QUICK = bool(os.environ.get("BENCH_QUICK"))
T_TRAIN = 4.0 if QUICK else 12.0
T_GEN = 4.0 if QUICK else 10.0


def _note(msg: str) -> None:
    """Progress marker on stderr (stdout stays one JSON line)."""
    import sys

    global _LAST_NOTE
    _LAST_NOTE = msg
    print(f"[bench +{time.perf_counter() - _T0:.0f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.perf_counter()
_LAST_NOTE = "startup"


def _env_float(name: str, default: float) -> float:
    """Env override parsed as float; malformed or SET-BUT-EMPTY values
    fall back to the default rather than costing the capture (an empty
    string from CI interpolation must not read as 0 and silently disable
    the deadline — explicit \"0\" is the disable switch)."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _deadline_s() -> float:
    """Outer wall-clock deadline for the WHOLE run (BENCH_DEADLINE_S,
    default 1700 s, 0 disables): stage starts budget against it, so the
    process finishes (or snapshots) BEFORE the driver's kill at roughly
    1,800 s."""
    return _env_float("BENCH_DEADLINE_S", 1700.0)


def _snapshot_path() -> str:
    return os.environ.get("BENCH_SNAPSHOT") or "bench_snapshot.json"


def _emit_snapshot(result: dict, final: bool = False) -> None:
    """Write the accumulated result as a complete JSON line to stdout AND
    atomically replace the side file — after the device lookup and after
    every stage — so a kill at ANY moment leaves the newest parseable
    snapshot behind.  Every line is the full result-so-far; a consumer
    taking the last parseable stdout line always gets the newest state.
    Non-final lines carry a "partial" marker naming where the run was."""
    snap = dict(result)
    snap["extra"] = dict(result.get("extra") or {})
    if final:
        snap.pop("partial", None)
    else:
        snap["partial"] = {
            "at": _LAST_NOTE,
            "elapsed_s": round(time.perf_counter() - _T0),
        }
    line = json.dumps(snap, default=str)
    print(line, flush=True)
    try:  # side file is best-effort; stdout is the contract
        path = _snapshot_path()
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(line + "\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError:
        pass


def _accelerator_devices():
    """The devices every stage measures on.  A measurement path that finds
    no accelerator raises: it never falls back to the CPU, whose rates
    must not be written under the device metric names."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise RuntimeError(
            "bench.py measures an accelerator and jax found only the CPU "
            f"({len(devices)} x {devices[0].device_kind}); nothing was measured"
        )
    return devices


def _peak_flops(device) -> float:
    from handyrl_tpu.parallel.train_step import peak_flops_per_chip

    return peak_flops_per_chip(device)


def _make_args(env_name: str, overrides=None, env_overrides=None):
    from handyrl_tpu.config import normalize_args

    cfg = normalize_args(
        {
            "env_args": {"env": env_name, **(env_overrides or {})},
            "train_args": dict(overrides or {}),
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    return args


def _fill_store(args, n_episodes: int):
    """Self-play episodes through the real generator with the zero-output
    RandomModel (host-side, no device calls) — data for the train benches."""
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, RandomModel, init_variables
    from handyrl_tpu.runtime import EpisodeStore, Generator

    env = make_env(args["env"])
    module = env.net()
    model = InferenceModel(module, init_variables(module, env))
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))

    store = EpisodeStore(max(n_episodes * 4, 1024))
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < n_episodes:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    return env, module, model, store


def _sample_batch(store, args):
    from handyrl_tpu.runtime import make_batch

    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(
            args["forward_steps"], args["burn_in_steps"], args["compress_steps"]
        )
        if w is not None:
            windows.append(w)
    return make_batch(windows, args)


def _timed_loop(step, duration: float) -> float:
    """Warm-compile then time: ``step()`` dispatches (possibly async)
    device work and returns a value to block on; the trailing
    block_until_ready is inside the measured window so enqueued work is
    fully accounted.  Returns calls/sec (always from >= 1 completed call:
    the window stretches rather than reporting a zero)."""
    import jax

    jax.block_until_ready(step())  # compile + warm
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < duration or n == 0:
        out = step()
        n += 1
        if n == 1:
            jax.block_until_ready(out)  # slow-backend case: 1 call > window
    jax.block_until_ready(out)
    return n / (time.perf_counter() - t0)


def _sig(x, digits: int = 3):
    """Round a rate to ``digits`` significant figures — never collapses a
    small-but-measured value to 0.0 the way fixed-decimal rounding did
    (round 2 reported geister_rnn_updates_per_sec: 0.0 for a measured
    0.0021/s)."""
    if x is None or x == 0:
        return x
    from math import floor, log10

    return round(x, max(digits - 1 - floor(log10(abs(x))), 0))


def _train_bench(env_name: str, overrides, duration: float, n_devices: int,
                 fill_episodes: int = 48, fused: bool = False, reuse=None,
                 env_overrides=None):
    """Timed jitted-train-step loop on pre-staged device batches.

    Returns updates/s, trained env-steps/s, flops/step (XLA cost analysis).
    ``reuse`` recycles a prior result's (module, model, store) so config
    variants (e.g. bf16) skip episode generation."""
    import jax

    from handyrl_tpu.parallel import TrainContext, make_mesh

    args = _make_args(env_name, overrides, env_overrides)
    if args["batch_size"] % n_devices:
        args["batch_size"] = max(n_devices, args["batch_size"] // n_devices * n_devices)

    if reuse is not None:
        module, model, store = reuse["module"], reuse["model"], reuse["store"]
        _note(f"{env_name}: reusing filled store; compiling + timing the train step")
    else:
        _note(f"{env_name}: generating episodes for the replay store")
        _, module, model, store = _fill_store(args, 12 if QUICK else fill_episodes)
        _note(f"{env_name}: store filled; compiling + timing the train step")

    mesh = make_mesh(args["mesh"])
    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(model.variables["params"])
    device_batches = [ctx.put_batch(_sample_batch(store, args)) for _ in range(4)]

    flops = ctx.flops_per_step(state, device_batches[0])

    holder = {"state": state, "i": 0}

    # FF compaction can give the staged batches distinct live-prefix
    # shapes; warm-compile every DISTINCT shape outside the timed window
    # (one cold compile inside the loop skews a 12 s window badly).  Only
    # distinct ones: an extra no-op warm costs a full update, which on a
    # slow backend (DRC on 1-core CPU: minutes) is far from free.
    def _shape_key(b):
        return tuple(
            (x.shape, str(x.dtype)) for x in jax.tree.leaves(b["observation"])
        )

    seen = {_shape_key(device_batches[0])}
    for b in device_batches[1:]:
        k = _shape_key(b)
        if k in seen:
            continue
        seen.add(k)
        holder["state"], m = ctx.train_step(holder["state"], b, 1e-5)
        jax.block_until_ready(m["total"])

    def seq_step():
        holder["state"], metrics = ctx.train_step(
            holder["state"], device_batches[holder["i"] % 4], 1e-5
        )
        holder["i"] += 1
        return metrics["total"]

    ups = _timed_loop(seq_step, duration)

    # fused_steps variant (k below): same updates through the lax.scan path — the
    # dispatch-amortization headroom for small models (config: fused_steps).
    # Opt-in per stage: big recurrent models pay a second long compile for
    # little dispatch-amortization benefit.  TPU-only: XLA:CPU executes
    # scan bodies single-threaded (measured 10-20x slower than unrolled).
    fused_ups = None
    fused_err = None
    if fused and jax.default_backend() == "tpu":
        try:
            # k=16 (was 8, round 3): where the step is dispatch-bound the
            # fused rate is ~(k x updates)/dispatch, so doubling the scan
            # depth costs negligible memory (16 stacked TicTacToe
            # batches) and a one-off compile
            k = 16
            stacked = ctx.put_batches([_sample_batch(store, args) for _ in range(k)])

            def fused_step():
                holder["state"], metrics = ctx.train_steps(holder["state"], stacked, 1e-5)
                return metrics["total"]

            fused_ups = _timed_loop(fused_step, duration / 2) * k
        except Exception:
            fused_err = traceback.format_exc(limit=3)

    return {
        "updates_per_sec": ups,
        "fused_updates_per_sec": fused_ups,
        "fused_error": fused_err,
        "trained_env_steps_per_sec": ups * args["batch_size"] * args["forward_steps"],
        "flops_per_step": flops,
        "store": store,
        "args": args,
        "ctx": ctx,
        "module": module,
        "model": model,
    }


def _generation_bench(env_name: str, overrides, duration: float, num_actors: int = 16):
    """Actor-plane throughput: thread actors sharing one device model via
    the BatchedInferenceEngine (runtime/inference_engine.py), counting
    env-steps completed in the timed window."""
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables
    from handyrl_tpu.runtime import Generator
    from handyrl_tpu.runtime.inference_engine import BatchedInferenceEngine, EngineStopped

    args = _make_args(env_name, overrides)
    env0 = make_env(args["env"])
    module = env0.net()
    model = InferenceModel(module, init_variables(module, env0))

    # pre-compile every power-of-two inference bucket OUTSIDE the timed
    # window (each distinct batch shape is one XLA compile)
    max_batch = min(args["inference_batch_size"], 4 * num_actors)
    _note(f"{env_name}: warming inference buckets up to {max_batch}")
    from handyrl_tpu.utils import tree_stack

    env0.reset()
    obs0 = env0.observation(env0.players()[0])
    b = 1
    while b <= max_batch:
        model.inference_batch(tree_stack([obs0] * b), None)
        b *= 2
    engine = BatchedInferenceEngine(model, max_batch=max_batch).start()
    _note(f"{env_name}: timing generation for {duration:.0f}s")

    steps = [0] * num_actors
    stop = threading.Event()

    def actor(i):
        env = make_env(args["env"])

        def count():
            steps[i] += 1  # incremental: long episodes still register

        gen = Generator(env, args, on_step=count)
        players = env.players()
        models = {p: engine.client() for p in players}
        gen_args = {"player": players, "model_id": {p: -1 for p in players}}
        while not stop.is_set():
            try:
                gen.generate(models, gen_args)
            except EngineStopped:
                return

    threads = [threading.Thread(target=actor, args=(i,), daemon=True) for i in range(num_actors)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    dt = time.perf_counter() - t0  # counting window ends here, before teardown
    engine.stop()
    for t in threads:
        t.join(timeout=5.0)
    total = sum(steps)
    return {
        "env_steps_per_sec": total / dt,
        "episodes_completed": None,
        "batches_served": engine.batches_served,
        "mean_infer_batch": (engine.requests_served / max(engine.batches_served, 1)),
    }


def _timed_pipeline_train(pipe, ctx, state, duration: float, on_timed_start=None,
                          on_timed_end=None):
    """Warm the train path on one pipeline batch, then time updates fed by
    the pipeline, accounting time spent waiting on input separately.
    Stretches past ``duration`` until >= 1 update completes (never a
    silent zero).  ``on_timed_start`` fires after the warm-up, right
    before the clock starts, and ``on_timed_end`` the moment the window
    closes — e.g. to launch a concurrent producer and snapshot its
    counters in sync with the window (work the producer retires after the
    window must not land in the numerator).  Returns
    (n_updates, wait_s, dt)."""
    import jax

    batch = pipe.batch()
    state, metrics = ctx.train_step(state, batch, 1e-5)  # compile path warm
    jax.block_until_ready(metrics["total"])

    if on_timed_start is not None:
        on_timed_start()
    wait_s = 0.0
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration or n == 0:
        tw = time.perf_counter()
        batch = pipe.batch()
        wait_s += time.perf_counter() - tw
        if batch is None:
            break
        state, metrics = ctx.train_step(state, batch, 1e-5)
        n += 1
    jax.block_until_ready(metrics["total"])
    dt = time.perf_counter() - t0
    if on_timed_end is not None:
        on_timed_end()
    return n, wait_s, dt


def _pipeline_bench(train_res, duration: float):
    """Train through the configured batch pipeline (default: shared-memory
    batcher PROCESSES, runtime/shm_batch.py; replay -> make_batch ->
    device_put -> step) and measure input starvation (north-star: learner
    never input-starved) plus the per-stage time breakdown that says
    WHICH stage any starvation comes from."""
    from handyrl_tpu.runtime.trainer import make_pipeline

    args, ctx, store = train_res["args"], train_res["ctx"], train_res["store"]
    stop = threading.Event()
    pipe = make_pipeline(args, store, ctx, stop)
    pipe.start()
    state = ctx.init_state(train_res["model"].variables["params"])
    window = {}

    # snapshot the cumulative stage counters exactly at the timed window's
    # edges, so warm-up assembly never lands in the breakdown
    n, wait_s, dt = _timed_pipeline_train(
        pipe, ctx, state, duration,
        on_timed_start=lambda: window.update(t0=pipe.stats()),
        on_timed_end=lambda: window.update(t1=pipe.stats()),
    )
    stop.set()
    pipe.stop()
    s0, s1 = window.get("t0", {}), window.get("t1", {})
    from handyrl_tpu.runtime.trainer import PIPE_STAT_KEYS

    stages = {
        key: round(s1.get(key, 0.0) - s0.get(key, 0.0), 4)
        for key in PIPE_STAT_KEYS
    }
    gets = s1.get("gets", 0.0) - s0.get("gets", 0.0)
    stages["device_queue_depth"] = round(
        (s1.get("device_queue_depth_sum", 0.0)
         - s0.get("device_queue_depth_sum", 0.0)) / gets, 3
    ) if gets else None
    stages["mode"] = s1.get("mode")
    return {
        "updates_per_sec": n / dt,
        "trained_env_steps_per_sec": n * args["batch_size"] * args["forward_steps"] / dt,
        "input_wait_frac": wait_s / dt,
        "stages": stages,
    }


def _pipeline_scaling_bench(train_res, duration: float):
    """northstar4: the host-pipeline scaling curve + the host-bypass path,
    side by side over ONE episode store (ROADMAP item 3).

    BENCH_r05 measured the chip eating 376 direct updates/s while the
    host-fed pipeline delivered 3.0 — and the shm plane had never been
    shown to scale past one child.  This stage measures exactly that:
    the shm plane at num_batchers 1/2/4 (updates/s, input_wait_frac,
    per-stage breakdown each), then ``batch_pipeline: device`` — episodes
    uploaded once into device rings, windows assembled on device
    (runtime/device_batch.py) — and evaluates every point against the
    direct updates/s from geese-train (target: host-fed >= 50% of direct
    with input_wait_frac < 0.05).
    """
    from handyrl_tpu.runtime.trainer import PIPE_STAT_KEYS, make_pipeline

    args, ctx, store = train_res["args"], train_res["ctx"], train_res["store"]
    params = train_res["model"].variables["params"]
    per_point = max(2.0, duration / 2)

    def timed_point(cfg_over):
        cfg = dict(args, **cfg_over)
        stop = threading.Event()
        pipe = make_pipeline(cfg, store, ctx, stop)
        pipe.start()
        state = ctx.init_state(params)
        window = {}
        n, wait_s, dt = _timed_pipeline_train(
            pipe, ctx, state, per_point,
            on_timed_start=lambda: window.update(t0=pipe.stats()),
            on_timed_end=lambda: window.update(t1=pipe.stats()),
        )
        stop.set()
        pipe.stop()
        s0, s1 = window.get("t0", {}), window.get("t1", {})
        return {
            "updates_per_sec": n / dt,
            "input_wait_frac": wait_s / dt,
            "mode": s1.get("mode"),
            "stages": {
                key: round(s1.get(key, 0.0) - s0.get(key, 0.0), 4)
                for key in PIPE_STAT_KEYS
            },
        }

    points = {}
    for nb in (1, 2, 4):
        _note(f"northstar4: shm plane, num_batchers={nb}")
        points[f"host_b{nb}"] = timed_point(
            {"batch_pipeline": "shm", "num_batchers": nb}
        )
    # stage geometry sized to the STORE: a chunk flushes only when every
    # lane has chunk steps queued, so on a small static store the default
    # lanes x chunk would never become sampleable and batch() would wait
    # forever (host-generated geese episodes run ~5 steps, not hundreds)
    total_steps = sum(int(ep["steps"]) for ep in store.snapshot())
    dp = ctx.mesh.shape.get("dp", 1)
    # a chunk is INGEST granularity, not window length — windows span
    # chunks, so it only needs to leave half the store flushable
    chunk = max(1, min(64, total_steps // (2 * dp)))
    _note(f"northstar4: host-bypass device stage ({dp} lanes x chunk {chunk})")
    points["device"] = timed_point({
        "batch_pipeline": "device",
        "device_stage_lanes": dp,
        "device_stage_chunk": chunk,
        "device_stage_slots": max(
            int(args.get("device_stage_slots", 1024)), 2 * chunk
        ),
    })

    direct = train_res["updates_per_sec"]
    best_host = max(
        (k for k in points if k.startswith("host_")),
        key=lambda k: points[k]["updates_per_sec"],
    )

    def target_met(p):
        return bool(
            direct
            and p["updates_per_sec"] >= 0.5 * direct
            and p["input_wait_frac"] < 0.05
        )

    return {
        "points": points,
        "direct_updates_per_sec": direct,
        "best_host": best_host,
        "best_host_vs_direct": points[best_host]["updates_per_sec"] / direct
        if direct else None,
        "device_vs_direct": points["device"]["updates_per_sec"] / direct
        if direct else None,
        "host_target_met": target_met(points[best_host]),
        "device_target_met": target_met(points["device"]),
    }


def _device_selfplay_bench(duration: float):
    """Fully on-device self-play (runtime/device_rollout.py): env stepping
    + inference + sampling in ONE jit call over thousands of parallel
    games (2048 on TPU, 512 on CPU) — the actor plane with zero host
    round-trips."""
    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.envs.vector_tictactoe import VectorTicTacToe
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.runtime.device_rollout import build_selfplay_fn

    env = make_env({"env": "TicTacToe"})
    module = env.net()
    params = init_variables(module, env)["params"]
    # 2048 parallel games on TPU (512 on CPU): per-dispatch work is what
    # amortizes the dispatch overhead, and the whole vectorized board
    # state is tiny next to HBM
    n_games = 2048 if jax.default_backend() == "tpu" else 512
    fn = build_selfplay_fn(VectorTicTacToe, module, n_games)

    holder = {"key": jax.random.PRNGKey(0)}

    def call():
        holder["key"], sub = jax.random.split(holder["key"])
        cols = fn(params, sub)
        holder["last"] = cols
        return cols["alive"]

    calls_per_sec = _timed_loop(call, duration)
    alive_per_call = float(jax.device_get(holder["last"]["alive"]).sum())
    return {
        "env_steps_per_sec": calls_per_sec * alive_per_call,
        "episodes_per_sec": calls_per_sec * n_games,
    }


def _streaming_selfplay_bench(env_name: str, overrides, duration: float,
                              n_lanes: int = 256, k_steps: int = 32):
    """Streaming on-device self-play: persistent lanes with auto-reset,
    env stepping + net inference + sampling in one jit per k_steps block
    (runtime/device_rollout.py:StreamingDeviceRollout).  This is the
    actor plane with zero host round-trips per step; episode assembly
    (compact-record -> columnar) runs inside the timed window, so the
    number is end-to-end."""
    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.runtime.device_rollout import StreamingDeviceRollout

    args = _make_args(env_name, overrides)
    env = make_env(args["env"])
    module = env.net()
    params = init_variables(module, env)["params"]
    roll = StreamingDeviceRollout(
        env.vector_env(), module, args, n_lanes=n_lanes, k_steps=k_steps
    )
    key = jax.random.PRNGKey(0)
    key, sub = jax.random.split(key)
    roll.generate(params, sub)  # compile + warm
    steps0, psteps0 = roll.game_steps, roll.player_steps
    n_eps = 0
    t0 = time.perf_counter()
    # adaptive window: stretch (up to 4x) until at least one episode has
    # completed, so episodes/sec is a measurement, not a silent 0.0 on a
    # slow backend; if even that fails, report null with the reason
    while True:
        dt = time.perf_counter() - t0
        if dt >= duration and (n_eps > 0 or dt >= 4 * duration):
            break
        key, sub = jax.random.split(key)
        n_eps += len(roll.generate(params, sub))
    dt = time.perf_counter() - t0  # before drain: the drained block's steps
    roll.drain()                   # are never counted, so its runtime must
    return {                       # not land in the denominator either
        "env_steps_per_sec": (roll.game_steps - steps0) / dt,
        "player_steps_per_sec": (roll.player_steps - psteps0) / dt,
        "episodes_per_sec": n_eps / dt if n_eps else None,
        "episodes_note": None if n_eps else f"no episode completed in {dt:.0f}s window",
        "lanes": n_lanes,
        "k_steps": k_steps,
    }


def _concurrent_northstar_bench(train_res, duration: float,
                                n_lanes: int = 256, k_steps: int = 32):
    """The north-star loop on ONE chip: streaming on-device self-play
    FEEDING the replay store while the learner trains from it concurrently
    — the architecture that replaces the reference's host worker tree
    (worker.py:110-189).  Captures both planes' rates plus learner input
    starvation; BASELINE.json's target is 100k env-steps/s on a v4-32
    with the learner never starved, i.e. ~3,125 env-steps/s per chip."""
    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.runtime import EpisodeStore
    from handyrl_tpu.runtime.device_rollout import StreamingDeviceRollout
    from handyrl_tpu.runtime.trainer import make_pipeline

    args, ctx, module = train_res["args"], train_res["ctx"], train_res["module"]
    env = make_env(args["env"])
    params = train_res["model"].variables["params"]
    if jax.default_backend() != "tpu":
        # fewer lanes so the ~200-step geese episodes start completing
        # within the prefill budget on a slow backend
        n_lanes = min(n_lanes, 32)
    roll = StreamingDeviceRollout(
        env.vector_env(), module, args, n_lanes=n_lanes, k_steps=k_steps,
        mesh=ctx.mesh,
    )
    store = EpisodeStore(8192)
    stop = threading.Event()
    holder = {"key": jax.random.PRNGKey(1), "rollout_error": None}

    def rollout_step():
        holder["key"], sub = jax.random.split(holder["key"])
        eps = roll.generate(params, sub)
        if eps:
            store.extend(eps)

    def rollout_loop():
        try:
            while not stop.is_set():
                rollout_step()
        except Exception:
            holder["rollout_error"] = traceback.format_exc(limit=3)
        finally:
            roll.drain()

    # pre-fill OUTSIDE the timed window so the pipeline can sample at once
    _note(f"northstar: prefilling store via streaming self-play ({n_lanes} lanes)")
    t_fill = time.perf_counter()
    while len(store) < 2 * n_lanes and time.perf_counter() - t_fill < 10 * duration:
        rollout_step()
    if len(store) == 0:
        roll.drain()
        return {
            "skipped": (
                f"no episode completed in the {time.perf_counter() - t_fill:.0f}s "
                f"prefill budget ({n_lanes} lanes)"
            )
        }

    pipe_stop = threading.Event()
    pipe = make_pipeline(args, store, ctx, pipe_stop)
    pipe.start()
    state = ctx.init_state(params)

    _note(f"northstar: {len(store)} episodes staged; timing concurrent train+selfplay")
    thread = threading.Thread(target=rollout_loop, daemon=True)
    counters = {"steps0": 0, "steps1": 0}

    def launch_producer():
        counters["steps0"] = roll.game_steps
        thread.start()

    def snapshot_producer():
        # inside the window only: blocks the producer retires after the
        # clock stops must not inflate the rate
        counters["steps1"] = roll.game_steps

    n, wait_s, dt = _timed_pipeline_train(
        pipe, ctx, state, duration,
        on_timed_start=launch_producer, on_timed_end=snapshot_producer,
    )
    stop.set()
    pipe_stop.set()
    pipe.stop()
    thread.join(timeout=120.0)
    selfplay_rate = (counters["steps1"] - counters["steps0"]) / dt
    # the lanes shard over the mesh: the aggregate rate divides over every
    # participating device before comparison against the 3,125/chip target
    n_chips = ctx.mesh.size
    out = {
        "trained_env_steps_per_sec": n * args["batch_size"] * args["forward_steps"] / dt,
        "selfplay_env_steps_per_sec": selfplay_rate,
        "input_wait_frac": wait_s / dt,
        "episodes_in_store": len(store),
        "per_chip_northstar_frac": selfplay_rate / (3125.0 * n_chips),
    }
    if holder["rollout_error"]:
        out["rollout_error"] = holder["rollout_error"]
    return out


def _device_replay_northstar_bench(train_res, duration: float,
                                   n_lanes: int = 128, k_steps: int = 32,
                                   fused_steps: int = 8,
                                   trains_per_rollout: int = 16):
    """The north-star loop with the DEVICE-RESIDENT replay
    (runtime/device_replay.py): streaming self-play records are ingested
    into on-device ring buffers and training batches are sampled,
    assembled, and stepped in one dispatch — the data path never touches
    the host (VERDICT r2 item 2 follow-up: the v1 loop was bounded by a
    ~43 MB obs upload per update plus every episode round-tripping
    device->host->device).  One iteration = 1 rollout call (k_steps x
    n_lanes game steps) + ``trains_per_rollout`` fused train calls
    (each fused_steps updates), self-play always running under the
    LATEST params.  The train:rollout call ratio sets the chip's duty
    split.  Defaults are the round-4 sweep's best point
    (tools/tune_northstar.py on the v5e, 2026-08-01: 128 lanes x k=32,
    fused 8 x trains 16 -> 176,867 trained steps/s vs 90,683 at the old
    256/2 geometry).  The sweep also settled WHY rollout_time_frac
    cannot reach <= 0.5 here: one self-play env-step costs ~100x one
    trained env-step in device time (sequential small-batch stepping vs
    big batched matmuls), so every geometry stays production-bound —
    raising trains_per_rollout buys trained throughput by re-sampling
    ring windows (produce_consume 0.016 at the tuned point = each
    sample seen ~60x, an off-policy replay-ratio regime the V-Trace/UPGO
    corrections exist for, cf. the soak passes at produce_consume
    well below 1)."""
    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.runtime.device_replay import DeviceReplay
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn

    args, ctx, module = train_res["args"], train_res["ctx"], train_res["module"]
    env = make_env(args["env"])
    venv = env.vector_env()
    if jax.default_backend() != "tpu":
        n_lanes = min(n_lanes, 32)
        fused_steps = min(fused_steps, 2)  # CPU unrolls the fused scan
    mesh = ctx.mesh
    fn = build_streaming_fn(
        venv, module, n_lanes, k_steps,
        mesh=mesh if mesh.size > 1 else None,
        use_observe_mask=bool(args.get("observation", False)),
    )
    replay = DeviceReplay(venv, module, args, mesh, n_lanes, slots=512)
    state = ctx.init_state(train_res["model"].variables["params"])
    key = jax.random.PRNGKey(11)

    from handyrl_tpu.parallel.mesh import dispatch_serialized

    vstate = venv.init(n_lanes, jax.random.PRNGKey(12))
    hidden = module.initial_state((n_lanes, venv.num_players))

    def rollout():
        nonlocal vstate, hidden, key
        key, sub = jax.random.split(key)
        vstate, hidden, records = dispatch_serialized(
            lambda: fn(state["params"], vstate, hidden, sub), mesh
        )
        return replay.ingest(records)

    _note(f"northstar2: prefilling device rings ({n_lanes} lanes)")
    t_fill = time.perf_counter()
    while time.perf_counter() - t_fill < 10 * duration:
        rollout()
        if replay.eligible_count() >= args["batch_size"]:
            break
    else:
        return {
            "skipped": (
                f"no sampleable window after {time.perf_counter() - t_fill:.0f}s "
                f"of ring prefill ({n_lanes} lanes)"
            )
        }

    train = replay.train_fn(ctx, fused_steps=fused_steps)
    # warm both executables outside the timed window
    state, m = train(state, jax.random.PRNGKey(13), 1e-5)
    jax.block_until_ready(m["total"])

    _note("northstar2: timing the all-on-device loop")
    t0 = time.perf_counter()
    updates = 0
    stats = []
    rollout_s = 0.0
    while True:
        tr = time.perf_counter()
        # the rollout stays ASYNC: no per-iteration host sync on its
        # stats (the old block_until_ready here handicapped this fused
        # baseline vs the split-plane stage) — everything drains once
        # after the window.  rollout_s is therefore time spent IN the
        # dispatch: on CPU dispatch_serialized blocks until ready so the
        # duty split is exact; on TPU it is enqueue time only and the
        # trailing block below folds residual execution into dt.
        stats.append(rollout())
        rollout_s += time.perf_counter() - tr
        for _ in range(trains_per_rollout):
            key, sub = jax.random.split(key)
            state, m = train(state, sub, 1e-5)
            updates += fused_steps
        dt = time.perf_counter() - t0
        if dt >= duration and updates > 0:
            break
    jax.block_until_ready(m["total"])
    jax.block_until_ready(stats[-1]["episodes"])  # drain in-flight rollout work
    dt = time.perf_counter() - t0
    fetched = jax.device_get(stats)
    game_steps = sum(int(s["game_steps"]) for s in fetched)
    episodes = sum(int(s["episodes"]) for s in fetched)
    selfplay_rate = game_steps / dt
    n_chips = mesh.size
    consumed = updates * args["batch_size"] * args["forward_steps"] / dt
    return {
        # EFFECTIVE geometry (post the non-TPU clamps above) — sweep rows
        # must echo what actually ran, not what was requested
        "lanes": n_lanes,
        "k_steps": k_steps,
        "fused_steps": fused_steps,
        "trains_per_rollout": trains_per_rollout,
        "trained_env_steps_per_sec": consumed,
        "updates_per_sec": updates / dt,
        "selfplay_env_steps_per_sec": selfplay_rate,
        "rollout_time_frac": rollout_s / dt,
        "episodes": episodes,
        # >1: self-play produces faster than training consumes (fresh
        # data regime); <1: windows are re-sampled (replay-ratio regime).
        # The r4 sweep showed rollout_time_frac <= 0.5 is unreachable on
        # this loop (rollout env-steps cost ~100x trained env-steps in
        # device time), so the tuned default trades reuse for trained
        # throughput; 1/this ratio is the effective replay ratio.
        "produce_consume_ratio": selfplay_rate / consumed if consumed else None,
        "per_chip_northstar_frac": selfplay_rate / (3125.0 * n_chips),
        "loss_finite": bool(jax.numpy.isfinite(jax.device_get(m["total"]))),
    }


def _split_plane_northstar_bench(train_res, duration: float,
                                 actor_chips: Optional[int] = None,
                                 n_lanes: int = 128, k_steps: int = 32,
                                 fused_steps: int = 8,
                                 param_refresh_updates: int = 8):
    """North-star v3: DISAGGREGATED planes — self-play pinned to an actor
    mesh, training to a disjoint learner mesh, running CONCURRENTLY from
    two host threads under the per-device dispatch locks
    (parallel/mesh.py).  The fused loop (northstar2) is production-bound
    by construction: one self-play env-step costs ~100x one trained
    env-step in device time, so one program queue spends >90% of its time
    in rollout at every geometry (round-4 sweep).  Splitting the chips
    removes the time-slicing: the learner plane's rollout share drops to
    zero and the produce/consume ratio becomes a CHIP-ALLOCATION knob
    (actor_chips) instead of a duty-cycle compromise.

    Three phases: ring prefill, the actor plane STANDALONE (its unshared
    rate — the concurrency yardstick), then both planes concurrent.
    Reports per-plane duty, trained + self-play env-steps/s, the
    concurrent/standalone self-play ratio, realized param lag, and the
    cross-mesh transfer rate.

    Reading selfplay_concurrent_frac: on REAL accelerators every chip has
    its own compute, so ~1.0 means training cost self-play nothing.  On
    the VIRTUAL CPU mesh all devices share the host's physical cores, so
    the ratio measures core contention, not plane contention — there the
    architecture proof is rollout_time_frac = 0 with both planes
    progressing inside one window (the 4-device smoke in
    tests/test_plane.py asserts exactly that)."""
    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext
    from handyrl_tpu.parallel.mesh import dispatch_serialized, split_mesh
    from handyrl_tpu.runtime.device_replay import DeviceReplay
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn
    from handyrl_tpu.runtime.plane import PlaneParamCache, RecordTransfer

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": f"plane: split needs >= 2 devices, have {len(devices)}"}
    args, module = train_res["args"], train_res["module"]
    env = make_env(args["env"])
    venv = env.vector_env()
    if actor_chips is None:
        actor_chips = max(1, len(devices) // 2)
    if jax.default_backend() != "tpu":
        n_lanes = min(n_lanes, 32)
        # scan-bodied collectives across VIRTUAL devices run at
        # pathological speed on XLA:CPU (see Trainer's fused_steps guard)
        fused_steps = 1
    learner_mesh, actor_mesh = split_mesh(args.get("mesh"), actor_chips)
    ldp = learner_mesh.shape.get("dp", 1)
    adp = actor_mesh.shape.get("dp", 1)
    import math

    largs = dict(args)
    if largs["batch_size"] % ldp:
        largs["batch_size"] = max(ldp, largs["batch_size"] // ldp * ldp)
    # lanes shard over the actor mesh (rollout) AND the learner mesh
    # (rings): round to a multiple of both dp sizes
    lanes_q = ldp * adp // math.gcd(ldp, adp)
    n_lanes = max(lanes_q, n_lanes // lanes_q * lanes_q)

    ctx = TrainContext(module, largs, learner_mesh)
    params0 = train_res["model"].variables["params"]
    state = ctx.init_state(params0)
    fn = build_streaming_fn(
        venv, module, n_lanes, k_steps, mesh=actor_mesh,
        use_observe_mask=bool(args.get("observation", False)),
    )
    replay = DeviceReplay(venv, module, largs, learner_mesh, n_lanes, slots=512)
    xfer = RecordTransfer(learner_mesh)
    cache = PlaneParamCache(actor_mesh)
    cache.publish(params0, 0)

    key = jax.random.PRNGKey(21)
    vstate = venv.init(n_lanes, jax.random.PRNGKey(22))
    hidden = module.initial_state((n_lanes, venv.num_players))

    def rollout():
        nonlocal vstate, hidden, key
        _, params = cache.latest()
        key, sub = jax.random.split(key)
        vstate, hidden, records = dispatch_serialized(
            lambda: fn(params, vstate, hidden, sub), actor_mesh
        )
        return replay.ingest(xfer(records))

    _note(f"northstar3: prefilling rings ({n_lanes} lanes, "
          f"{len(devices) - actor_chips}+{actor_chips} learner+actor chips)")
    t_fill = time.perf_counter()
    while time.perf_counter() - t_fill < 10 * duration:
        rollout()
        if replay.eligible_count() >= largs["batch_size"]:
            break
    else:
        return {
            "skipped": (
                f"no sampleable window after {time.perf_counter() - t_fill:.0f}s "
                f"of ring prefill ({n_lanes} lanes)"
            )
        }

    train = replay.train_fn(ctx, fused_steps=fused_steps)
    state, m = train(state, jax.random.PRNGKey(23), 1e-5)  # warm the train path
    jax.block_until_ready(m["total"])

    def timed_rollout_window(t_window: float):
        """Drive the actor loop for ~t_window; (game_steps, busy_s, dt)."""
        stats, busy = [], 0.0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < t_window or not stats:
            tb = time.perf_counter()
            stats.append(rollout())
            busy += time.perf_counter() - tb
        jax.block_until_ready(stats[-1]["episodes"])
        dt = time.perf_counter() - t0
        fetched = jax.device_get(stats)
        return sum(int(s["game_steps"]) for s in fetched), busy, dt

    _note("northstar3: actor plane standalone")
    sa_steps, _, sa_dt = timed_rollout_window(duration / 2)
    standalone_rate = sa_steps / sa_dt

    _note("northstar3: timing both planes concurrently")
    stop = threading.Event()
    prod = {"steps": 0, "episodes": 0, "busy_s": 0.0, "lag_sum": 0.0,
            "dispatches": 0, "error": None}
    learner_updates = [0]

    def producer():
        stats, busy, lags = [], [], []
        n_window = 0
        try:
            while not stop.is_set():
                tb = time.perf_counter()
                lags.append(max(0, learner_updates[0] - cache.version))
                stats.append(rollout())
                busy.append(time.perf_counter() - tb)
                if not stop.is_set():  # blocks retired inside the window
                    n_window = len(stats)
        except Exception:
            prod["error"] = traceback.format_exc(limit=3)
        finally:
            if stats:
                jax.block_until_ready(stats[-1]["episodes"])
            # trim EVERY counter to the measurement window, or the frac/
            # lag denominators disagree with the steps they pair with
            # (the final rollout can outlive the learner window on CPU)
            fetched = jax.device_get(stats[:n_window])
            prod["steps"] = sum(int(s["game_steps"]) for s in fetched)
            prod["episodes"] = sum(int(s["episodes"]) for s in fetched)
            prod["busy_s"] = sum(busy[:n_window])
            prod["lag_sum"] = float(sum(lags[:n_window]))
            prod["dispatches"] = n_window

    on_cpu = jax.default_backend() == "cpu"
    thread = threading.Thread(target=producer, daemon=True)
    xfer_bytes0 = xfer.bytes_transferred + cache.bytes_transferred
    updates = 0
    train_s = 0.0
    rollout_s_learner = 0.0  # rollout work on the LEARNER thread: none
    tkey = jax.random.PRNGKey(24)
    thread.start()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration or updates == 0:
        tt = time.perf_counter()
        tkey, sub = jax.random.split(tkey)
        state, m = train(state, sub, 1e-5)
        train_s += time.perf_counter() - tt
        updates += fused_steps
        learner_updates[0] += fused_steps
        if learner_updates[0] - cache.version >= param_refresh_updates:
            cache.publish(state["params"], learner_updates[0])
        if on_cpu:
            # hand the learner-plane locks to the producer's ingest (the
            # same unfair-threading.Lock starvation the trainer's sleep
            # documents); on TPU dispatch is async and the gap never forms
            time.sleep(0.005)
    jax.block_until_ready(m["total"])
    dt = time.perf_counter() - t0
    stop.set()
    thread.join(timeout=120.0)
    if thread.is_alive() and not prod["error"]:
        # counters are only written in the producer's finally block — a
        # wedged rollout dispatch would otherwise report 0 self-play
        # env-steps/s as if it were a real measurement
        prod["error"] = "producer thread still running after 120s join timeout"
    selfplay_rate = prod["steps"] / dt
    consumed = updates * largs["batch_size"] * largs["forward_steps"] / dt
    out = {
        "actor_chips": actor_chips,
        "learner_chips": len(devices) - actor_chips,
        "lanes": n_lanes,
        "k_steps": k_steps,
        "fused_steps": fused_steps,
        "batch_size": largs["batch_size"],
        "param_refresh_updates": param_refresh_updates,
        "trained_env_steps_per_sec": consumed,
        "updates_per_sec": updates / dt,
        "selfplay_env_steps_per_sec": selfplay_rate,
        "selfplay_standalone_env_steps_per_sec": standalone_rate,
        # the concurrency proof: ~1.0 means training cost self-play
        # nothing (true disaggregation); the fused loop's equivalent is
        # its duty split
        "selfplay_concurrent_frac": selfplay_rate / standalone_rate
        if standalone_rate else None,
        # rollout work on the learner plane's program queue: structurally
        # zero — the split design's whole point (vs 0.91 fused, round 4)
        "rollout_time_frac": rollout_s_learner / dt,
        "learner_train_time_frac": train_s / dt,
        "actor_busy_frac": prod["busy_s"] / dt,
        "param_lag_mean": prod["lag_sum"] / max(prod["dispatches"], 1),
        "xfer_bytes_per_sec": (
            xfer.bytes_transferred + cache.bytes_transferred - xfer_bytes0
        ) / dt,
        "produce_consume_ratio": selfplay_rate / consumed if consumed else None,
        "per_chip_northstar_frac": selfplay_rate / (3125.0 * len(devices)),
        "episodes": prod["episodes"],
        "loss_finite": bool(jax.numpy.isfinite(jax.device_get(m["total"]))),
    }
    if prod["error"]:
        out["rollout_error"] = prod["error"]
    return out


# child for the northstar3mp leg: one rank of a 2-process pod-slice run —
# 4 virtual CPU devices carved 2 learner (global collective mesh) + 2
# actor (process-local rollout/rings), the full Learner epoch loop
_NORTHSTAR3MP_CHILD = r"""
import json, os, sys

port, hport, pid, nproc, outdir, epochs = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5], int(sys.argv[6]),
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from handyrl_tpu.config import normalize_args
from handyrl_tpu.parallel import init_distributed

dist = {
    "coordinator_address": f"127.0.0.1:{port}",
    "num_processes": nproc,
    "process_id": pid,
    "initialization_timeout": 180.0,
    "heartbeat_interval": 1.0,
    "heartbeat_timeout": 60.0,
    "collective_timeout": 300.0,
    "health_port": hport,
}
init_distributed(dist)
train = {
    "plane": "split",
    "actor_chips": 2,
    "param_refresh_updates": 2,
    # both ranks compile concurrently on shared cores: the default 120s
    # stall bound would degrade a healthy run split -> fused mid-leg
    "plane_stall_timeout": 600.0,
    "mesh": {"dp": -1},
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
    "maximum_episodes": 10 ** 6,
    "epochs": epochs,
    "num_batchers": 0,
    "batch_pipeline": "thread",
    "eval_rate": 0.0,
    "worker": {"num_parallel": 1},
    "model_dir": os.path.join(outdir, f"models_{pid}"),
    "metrics_path": os.path.join(outdir, f"metrics_{pid}.jsonl"),
    "distributed": dist,
}
args = normalize_args(
    {"env_args": {"env": "ParallelTicTacToe"}, "train_args": train}
)

from handyrl_tpu.runtime.learner import Learner

code = Learner(args).run()

from handyrl_tpu.parallel.distributed import shutdown_distributed

shutdown_distributed()
sys.exit(code)
"""


def _multiprocess_split_plane_bench(epochs: int = 3):
    """North-star v3, POD-SLICE leg (northstar3mp): the same split-plane
    loop as northstar3 but across TWO real OS processes under
    jax.distributed — each rank carves its 4 virtual CPU devices 2+2
    (global collective learner mesh over DCN + process-local actor plane)
    and the per-rank shards meet the collective train step through the
    make_array_from_process_local_data seam.

    Subprocess-based and CPU-forced BY DESIGN: two processes cannot share
    one accelerator, and this leg measures the pod-slice topology's
    mechanics (collective stepping under per-rank device planes, cadence
    agreement, the plane duty/transfer keys) rather than chip throughput
    — the single-process northstar3 stage owns that number.  The
    acceptance is concurrency: some coordinator epoch must show BOTH
    planes' rates nonzero in the same window."""
    import socket
    import subprocess
    import sys
    import tempfile

    def free_port():
        s = socket.socket()
        s.bind(("", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    batch_size, forward_steps = 8, 4  # mirrors _NORTHSTAR3MP_CHILD
    with tempfile.TemporaryDirectory(prefix="ns3mp_") as outdir:
        port, hport = free_port(), free_port()
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.dirname(os.path.abspath(__file__))
        _note(f"northstar3mp: spawning 2 learner ranks ({epochs} epochs)")
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _NORTHSTAR3MP_CHILD, str(port),
                 str(hport), str(pid), "2", outdir, str(epochs)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
            for pid in range(2)
        ]
        try:
            outs = [
                p.communicate(timeout=900)[0].decode(errors="replace")
                for p in procs
            ]
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            return {"skipped": "northstar3mp children timed out after 900s"}
        if any(p.returncode != 0 for p in procs):
            return {"skipped": "northstar3mp child failed: rc=%s\n%s" % (
                [p.returncode for p in procs],
                "".join(o[-2000:] for o in outs),
            )}
        records = [
            json.loads(l)
            for l in open(os.path.join(outdir, "metrics_0.jsonl"))
            if l.strip()
        ]
    epoch_rows = [r for r in records if "plane_actor_busy_frac" in r]
    if not epoch_rows:
        return {"skipped": "no plane_* epoch rows in coordinator metrics"}
    both = [
        r for r in epoch_rows
        if r.get("updates_per_sec", 0) > 0 and r.get("episodes_per_sec", 0) > 0
    ]
    best = max(epoch_rows, key=lambda r: r.get("updates_per_sec", 0))
    return {
        "processes": 2,
        "epochs": len(epoch_rows),
        "updates_per_sec": best.get("updates_per_sec", 0.0),
        "trained_env_steps_per_sec": (
            best.get("updates_per_sec", 0.0) * batch_size * forward_steps
        ),
        "episodes_per_sec": best.get("episodes_per_sec", 0.0),
        "actor_busy_frac": max(r["plane_actor_busy_frac"] for r in epoch_rows),
        "xfer_bytes_per_sec": max(
            r.get("plane_xfer_bytes_per_sec", 0.0) for r in epoch_rows
        ),
        "both_planes_concurrent": bool(both),
        "dist_processes": records[-1].get("dist_processes"),
    }


def _geister_device_replay_bench(duration: float):
    """Turn-mode device-resident replay (runtime/device_replay.py turn
    mode): Geister's DRC ConvLSTM trained straight from device rings —
    all-player windows with 4 real burn-in rows + UPGO — concurrent with
    turn-based streaming self-play, same loop shape as northstar2.  The
    on-chip soak this measures the steady state of trained wp 0.519->0.694
    vs random in ~10 min (BASELINE.md)."""
    from types import SimpleNamespace

    import jax

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.parallel import TrainContext, make_mesh

    args = _make_args(
        "Geister",
        {"turn_based_training": True, "observation": True,
         "batch_size": 16, "forward_steps": 8, "burn_in_steps": 4,
         "policy_target": "UPGO", "value_target": "UPGO"},
    )
    n_devices = len(jax.devices())
    if args["batch_size"] % n_devices:  # same guard as _train_bench
        args["batch_size"] = max(n_devices, args["batch_size"] // n_devices * n_devices)
    env = make_env(args["env"])
    module = env.net()
    ctx = TrainContext(module, args, make_mesh(args["mesh"]))
    train_res = {"args": args, "ctx": ctx, "module": module,
                 "model": SimpleNamespace(variables=init_variables(module, env))}
    # trains_per_rollout pinned at the r3 value: the tuned default (16) is
    # a HungryGeese-sweep result; Geister's recurrent rows must stay
    # comparable with the recorded r3/r4 captures (80.1 / 79.1 updates/s)
    return _device_replay_northstar_bench(
        train_res, duration, n_lanes=64, k_steps=32, fused_steps=4,
        trains_per_rollout=2,
    )


def _flash_attention_bench(duration: float = 3.0):
    """Masked Pallas flash kernel vs exact einsum on the transformer
    seq-mode semantics (fwd+bwd), at a long-window shape where the O(T^2)
    score tensor starts to matter.  Records the speedup that justifies
    seq_attention='auto' dispatching to the kernel on TPU."""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.ops.flash_attention import (
        masked_attention_reference,
        masked_flash_attention,
    )

    B, T, H, D = 8, 1024, 4, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    key_mask = jnp.ones((B, T), jnp.float32)
    slopes = 2.0 ** (-jnp.arange(1, H + 1, dtype=jnp.float32))

    def timed(fn):
        # grad wrt q, k AND v — in training all three come from trained
        # params, so the dk/dv backward path must be in the timing
        loss = jax.jit(
            jax.grad(
                lambda q, k, v: (fn(q, k, v, key_mask, slopes) ** 2).sum(),
                argnums=(0, 1, 2),
            )
        )
        return 1000.0 / _timed_loop(lambda: loss(q, k, v), duration)  # ms/call

    flash_ms = timed(masked_flash_attention)
    einsum_ms = timed(masked_attention_reference)
    return {
        "shape": f"B{B} T{T} H{H} D{D}",
        "flash_ms": round(flash_ms, 2),
        "einsum_ms": round(einsum_ms, 2),
        "speedup": round(einsum_ms / flash_ms, 2),
    }


# ---------------------------------------------------------------------------
# transformer_long: the long-context train step at production shapes
# (ROADMAP item 5) — T x attention-mode sweep + an sp=2 ring leg
# ---------------------------------------------------------------------------

# module-level pins so CI can trace/exercise the exact sweep geometry
# (same contract as TRANSFORMER_TPU_NET_ARGS below).  TPU: the d1536 knee
# shape from the 2026-08-02 width sweep, batch shrinking with T so the
# remat ladder (auto -> 'block' at T >= 512) is what fits T1024 in HBM,
# not a vanishing batch.  CPU: tiny shapes through the IDENTICAL code
# path — interpret-mode Pallas for the flash points, flash_min_t lowered
# so the 'auto' points exercise both sides of the crossover.
TRANSFORMER_LONG_TPU = {
    "net_args": {"d_model": 1536, "n_heads": 16, "n_layers": 8,
                 "memory_len": 32},
    "sweep_t": (64, 512, 1024),
    "batch_by_t": {64: 64, 512: 16, 1024: 8},
    "flash_min_t": 128,
    "compute_dtype": "bfloat16",
    "sp_t": 512,
    "sp_batch": 16,
}
TRANSFORMER_LONG_CPU = {
    "net_args": {"d_model": 64, "n_heads": 2, "n_layers": 2,
                 "memory_len": 16},
    "sweep_t": (8, 16, 32),
    "batch_by_t": {8: 8, 16: 8, 32: 8},
    "flash_min_t": 16,
    "compute_dtype": "float32",
    "sp_t": 16,
    "sp_batch": 8,
}
TRANSFORMER_LONG_MFU_TARGET = 0.40


def _compiled_peak_bytes(ctx, state, batch):
    """Peak on-device bytes of the bound train step, from XLA's compiled
    memory analysis (temp + arguments + outputs).  AOT-compiles the same
    program a second time, so callers only invoke it where that is cheap
    (CPU) or worth a few minutes (the longest-T points of a real-TPU
    capture, where the remat ladder's HBM story is the point)."""
    import jax
    import jax.numpy as jnp

    try:
        lowered = ctx._bind(state).lower(
            state, batch, jax.ShapeDtypeStruct((), jnp.float32)
        )
        ma = lowered.compile().memory_analysis()
        if ma is None:
            return None
        total = 0
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes"):
            total += int(getattr(ma, attr, 0) or 0)
        return total or None
    except Exception:
        return None


def _transformer_long_bench(duration: float, n_dev: int, peak):
    """One training semantics from T64 on one chip to T1024 across an sp
    mesh: sweep T x seq_attention {einsum, flash, auto} through the SAME
    TrainContext path as every other stage (real Geister windows, real
    losses, Adam), plus a dp x sp ring-attention leg — each point
    reporting updates/s, tokens/s, MFU and (where measured) peak device
    bytes, judged against transformer_long_mfu >= 0.40.

    The remat ladder rides along as 'auto' (resolve_seq_remat: 'block' at
    T >= 512 on TPU), and the remat-none memory headroom at the longest T
    is recorded from an AOT compile of the same program — the
    OOM-by-construction comparison that motivated the ladder."""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.parallel import resolve_seq_attention, resolve_seq_remat
    from handyrl_tpu.parallel.train_step import TrainContext
    from handyrl_tpu.parallel.mesh import make_mesh

    on_tpu = jax.default_backend() == "tpu"
    pins = TRANSFORMER_LONG_TPU if on_tpu else TRANSFORMER_LONG_CPU
    env_over = {"net": "transformer", "net_args": pins["net_args"]}
    modes = ("einsum", "flash", "auto")
    per_point = max(1.5, duration / (len(pins["sweep_t"]) * len(modes) + 1))

    def overrides(T, mode, B):
        return {
            "batch_size": B, "burn_in_steps": 0, "forward_steps": T,
            "observation": True, "seq_attention": mode,
            "flash_min_t": pins["flash_min_t"],
            "compute_dtype": pins["compute_dtype"], "remat": "auto",
        }

    points = {}
    reuse = None
    mem_tr = None  # the longest-T point, kept for the memory comparison
    for T in pins["sweep_t"]:
        for mode in modes:
            B = pins["batch_by_t"][T]
            _note(f"transformer_long: T{T} {mode} B{B}")
            tr = _train_bench(
                "Geister", overrides(T, mode, B), per_point, n_dev,
                fill_episodes=8, reuse=reuse, env_overrides=env_over,
            )
            reuse = reuse or tr
            args = tr["args"]
            ups = tr["updates_per_sec"]
            tokens = args["batch_size"] * 2 * T  # 2 players per window row
            points[f"T{T}_{mode}"] = {
                "updates_per_sec": ups,
                "tokens_per_sec": ups * tokens,
                "attn": resolve_seq_attention(args, T),
                "remat": resolve_seq_remat(args, T),
                "mfu": (tr["flops_per_step"] * ups / (peak * n_dev))
                if tr["flops_per_step"] and peak else None,
                "peak_bytes": None,
            }
            if T == pins["sweep_t"][-1] and mode == "auto":
                mem_tr = tr
    # peak-memory story at the longest T: the remat-'block' program vs a
    # remat-'none' AOT compile of the SAME step (never executed — at
    # production shapes remat: none is the configuration that OOMs, the
    # d2048 width-sweep collapse)
    remat_headroom = None
    if mem_tr is not None:
        T_max = pins["sweep_t"][-1]
        args = mem_tr["args"]
        try:
            batch_host = _sample_batch(mem_tr["store"], args)
            mems = {}
            for rung in ("block", "none"):
                ctx = TrainContext(
                    mem_tr["module"], dict(args, remat=rung),
                    make_mesh(args["mesh"]),
                )
                state = ctx.init_state(mem_tr["model"].variables["params"])
                mems[rung] = _compiled_peak_bytes(
                    ctx, state, ctx.put_batch(batch_host)
                )
            # the point's peak_bytes must describe the program it MEASURED
            # (auto resolves 'none' on CPU, 'block' on TPU at long T); the
            # block-vs-none pair rides separately as remat_headroom
            measured_rung = points[f"T{T_max}_auto"]["remat"]
            points[f"T{T_max}_auto"]["peak_bytes"] = mems.get(measured_rung)
            if mems["block"] and mems["none"]:
                remat_headroom = {
                    "block": mems["block"], "none": mems["none"],
                    "ratio": round(mems["none"] / mems["block"], 3),
                }
        except Exception:
            _note("transformer_long: peak-memory comparison unavailable "
                  f"({traceback.format_exc(limit=1).splitlines()[-1]})")

    # sp=2 ring leg: the same train step with T sharded over an sp mesh
    sp_leg = None
    sp_note = None
    if n_dev >= 2:
        dp = max(n_dev // 2, 1)
        T, B = pins["sp_t"], pins["sp_batch"]
        B = max(dp, B // dp * dp)
        _note(f"transformer_long: sp=2 ring leg (dp{dp} x sp2, T{T} B{B})")
        tr = _train_bench(
            "Geister",
            dict(overrides(T, "ring", B), mesh={"dp": dp, "sp": 2}),
            per_point, dp, fill_episodes=8, reuse=reuse,
            env_overrides=env_over,
        )
        ups = tr["updates_per_sec"]
        sp_leg = {
            "updates_per_sec": ups,
            "tokens_per_sec": ups * tr["args"]["batch_size"] * 2 * T,
            "attn": "ring",
            "mfu": (tr["flops_per_step"] * ups / (peak * n_dev))
            if tr["flops_per_step"] and peak else None,
        }
    else:
        sp_note = "single device: no sp axis to shard over"

    mfus = [p["mfu"] for p in points.values() if p.get("mfu")]
    best = max(mfus) if mfus else None
    return {
        "points": points,
        "sp2": sp_leg,
        "sp2_note": sp_note,
        "remat_headroom": remat_headroom,
        "mfu": best,
        # judged on real-TPU captures; None (not false) where MFU cannot
        # be computed, so a CPU smoke never reads as a missed target
        "target_met": (best >= TRANSFORMER_LONG_MFU_TARGET)
        if best is not None and on_tpu else None,
    }


# the transformer stage's on-chip shape (module-level so CI can trace the
# EXACT program the driver bench will compile on the TPU — the stage is
# TPU-gated, so without that trace a shape bug would first surface
# mid-capture; tests/test_transformer.py::test_bench_tpu_transformer_config_traces)
# width sweep 2026-08-02 (all einsum, B64/T64): d1024 0.494, d1024/L16
# 0.489 (depth flat), d1536 0.597, d2048 0.185 (HBM pressure — remat/
# spill collapse at 20 TFLOP/step), d1024/B128 0.45 (batch flat).
# Width is the MFU lever until memory pressure bites; d1536 is the knee.
TRANSFORMER_TPU_NET_ARGS = {"d_model": 1536, "n_heads": 16, "n_layers": 8,
                            "memory_len": 32}
TRANSFORMER_TPU_OVERRIDES = {"batch_size": 64, "burn_in_steps": 2,
                             "forward_steps": 62, "observation": True,
                             "compute_dtype": "bfloat16",
                             # einsum at T64: settled on-chip at d1024
                             # (2026-08-02: einsum 18.6 updates/s / MFU
                             # 0.48 vs flash 13.5 / 0.347 — the O(T^2)
                             # term is tiny and XLA-fusable at T64 while
                             # the kernel pays fixed launch overhead), and
                             # the d1536 evidence so far agrees (einsum
                             # MFU 0.597 via tools/tune_transformer.py).
                             # The d1536 crossover now has a DEDICATED
                             # measurement: the transformer_long stage
                             # sweeps T {64, 512, 1024} x {einsum, flash,
                             # auto} at exactly this width — run
                             # BENCH_STAGES=transformer_long on the chip
                             # and re-pin from its T64 row if flash
                             # ever wins there.  'auto' (flash_min_t 128)
                             # picks einsum at T64 regardless; pinned
                             # explicitly so the stage measures one known
                             # program
                             "seq_attention": "einsum"}

# ---------------------------------------------------------------------------
# serving: the standalone inference serving plane under load (ROADMAP item 2)
# ---------------------------------------------------------------------------

# load-generator geometry (per phase; durations scale with T_TRAIN/QUICK)
SERVING_CLIENTS = 4 if QUICK else 8        # closed-loop connections
SERVING_WINDOW = 8                          # outstanding requests per conn
SERVING_SHED_SLO_MS = 25.0                  # tight budget for the shed legs


def _serving_bench(duration: float):
    """Latency-SLO bench of the serving plane (handyrl_tpu/serving) over
    the REAL framed-socket transport: closed-loop saturation QPS with
    client-measured p50/p99, shed rate at two offered loads against a
    tight SLO (shed-fast must engage under overload and stay quiet under
    it), and a hot-swap leg measuring time-to-first-response on the new
    model with a zero-drop count — the zero-downtime contract measured,
    not asserted."""
    import threading as _threading

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.serving import (
        ModelRouter, ServingClient, ServingError, ServingServer,
    )
    from handyrl_tpu.serving.batcher import percentiles_ms

    env = make_env({"env": "TicTacToe"})
    module = env.net()
    env.reset()
    obs = env.observation(0)
    p1 = init_variables(module, env, seed=1)["params"]
    p2 = init_variables(module, env, seed=2)["params"]

    base_cfg = {
        "port": 0, "max_models": 4, "slo_ms": 1000.0, "shed_policy": "none",
        "max_batch": 64, "max_wait_ms": 1.0,
        # every power-of-two bucket pre-warmed: real traffic reaches them
        # all, and a hot-path compile would both spike p99 and (pre-warm)
        # distort the admission EMA's first samples
        "warm_buckets": [1, 2, 4, 8, 16, 32, 64],
        "queue_bound": 8192, "recv_timeout": 0.0, "watch_interval": 0.0,
        "stats_interval": 0.0,
    }

    def start_server(**overrides):
        cfg = dict(base_cfg, **overrides)
        router = ModelRouter(module, obs, cfg, model_dir=".")
        router.publish(1, p1)
        return router, ServingServer(router, cfg).run()

    def closed_loop(port, dur, lat, counts, models=None, stop=None):
        """One connection keeping SERVING_WINDOW requests outstanding."""
        client = ServingClient("127.0.0.1", port)
        inflight = []
        end = time.perf_counter() + dur
        try:
            while time.perf_counter() < end and not (stop and stop.is_set()):
                while len(inflight) < SERVING_WINDOW:
                    inflight.append((time.perf_counter(), client.submit(obs)))
                t0, fut = inflight.pop(0)
                try:
                    reply = fut.result(timeout=120)
                    lat.append((time.perf_counter() - t0) * 1000.0)
                    counts["ok"] += 1
                    if models is not None:
                        models.append((time.perf_counter(), reply["model"]))
                except Exception:
                    counts["err"] += 1
            for _t0, fut in inflight:
                try:
                    fut.result(timeout=120)
                    counts["ok"] += 1
                except Exception:
                    counts["err"] += 1
        finally:
            client.close()

    out = {"clients": SERVING_CLIENTS, "window": SERVING_WINDOW}

    # -- phase 1: closed-loop saturation + latency percentiles ------------
    router, server = start_server()
    lats = [[] for _ in range(SERVING_CLIENTS)]
    counts = [dict(ok=0, err=0) for _ in range(SERVING_CLIENTS)]
    threads = [
        _threading.Thread(target=closed_loop,
                          args=(server.bound_port, duration, lats[i], counts[i]),
                          daemon=True)
        for i in range(SERVING_CLIENTS)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    total_ok = sum(c["ok"] for c in counts)
    all_lat = [x for l in lats for x in l]
    pct = percentiles_ms(all_lat)
    out["saturation_qps"] = total_ok / max(elapsed, 1e-6)
    out["p50_ms"] = pct[50]
    out["p99_ms"] = pct[99]
    out["requests"] = total_ok
    out["load_errors"] = sum(c["err"] for c in counts)

    # -- phase 3 (same server, still warm): hot-swap under load -----------
    stop = _threading.Event()
    swap_models = [[] for _ in range(max(2, SERVING_CLIENTS // 2))]
    swap_counts = [dict(ok=0, err=0) for _ in swap_models]
    threads = [
        _threading.Thread(target=closed_loop,
                          args=(server.bound_port, 120.0, [], swap_counts[i],
                                swap_models[i], stop),
                          daemon=True)
        for i in range(len(swap_models))
    ]
    for t in threads:
        t.start()
    time.sleep(min(1.0, duration / 4))
    admin = ServingClient("127.0.0.1", server.bound_port)
    t_swap = time.perf_counter()
    swap = admin.swap(2, params=p2)
    time.sleep(min(1.0, duration / 4))
    stop.set()
    for t in threads:
        t.join(60)
    admin.close()
    events = sorted(e for l in swap_models for e in l)
    new_times = [t for t, m in events if m == 2]
    seen = {m for _, m in events}
    out["swap_warm_ms"] = swap["warm_ms"]
    out["swap_ttfr_ms"] = (
        (new_times[0] - t_swap) * 1000.0 if new_times else None
    )
    out["swap_dropped"] = sum(c["err"] for c in swap_counts)
    out["swap_flip_observed"] = seen == {1, 2}
    server.shutdown()

    # -- phase 2: shed rate vs offered load (fresh server, tight SLO) -----
    def open_loop(port, rate, dur, counters):
        """Paced open-loop offered load over several connections (one
        socket serializing the whole rate would throttle the offer);
        callbacks sort the outcomes."""
        clients = [
            ServingClient("127.0.0.1", port)
            for _ in range(max(2, SERVING_CLIENTS // 2))
        ]
        lock = _threading.Lock()
        pending = [0]

        def cb(fut):
            try:
                fut.result()
                kind = "ok"
            except ServingError as exc:
                kind = "shed" if exc.kind in ("shed", "deadline") else "err"
            except Exception:
                kind = "err"
            with lock:
                counters[kind] = counters.get(kind, 0) + 1
                pending[0] -= 1

        start = time.perf_counter()
        sent = 0
        try:
            while time.perf_counter() - start < dur:
                due = int((time.perf_counter() - start) * rate) - sent
                for _ in range(min(max(due, 0), 512)):
                    with lock:
                        pending[0] += 1
                    clients[sent % len(clients)].submit(
                        obs, slo_ms=SERVING_SHED_SLO_MS
                    ).add_done_callback(cb)
                    sent += 1
                time.sleep(0.002)
            counters["offered"] = sent
            deadline = time.perf_counter() + 60.0
            while time.perf_counter() < deadline:
                with lock:
                    if pending[0] == 0:
                        break
                time.sleep(0.005)
        finally:
            for client in clients:
                client.close()

    sat = max(out["saturation_qps"], 1.0)
    router, server = start_server(shed_policy="deadline",
                                  slo_ms=SERVING_SHED_SLO_MS)
    for tag, rate in (("low", 0.25 * sat), ("high", 2.0 * sat)):
        counters: dict = {}
        open_loop(server.bound_port, rate, duration / 2, counters)
        offered = max(counters.get("offered", 0), 1)
        shed = counters.get("shed", 0)
        out[f"offered_{tag}_qps"] = counters.get("offered", 0) / (duration / 2)
        out[f"shed_rate_{tag}"] = shed / offered
        out[f"errors_{tag}"] = counters.get("err", 0)
    server.shutdown()
    return out


# ---------------------------------------------------------------------------
# fleet: the serving tier behind one router front (docs/serving.md §Fleet)
# ---------------------------------------------------------------------------

# stateful load geometry: each connection keeps one request outstanding
# per open session (the honest shape of recurrent traffic — a session's
# steps are serial by definition; concurrency comes from session count)
FLEET_CLIENTS = 4 if QUICK else 6
FLEET_SESSIONS = 8                    # sessions (and window) per connection
FLEET_RATIO_STEPS = 16                # serial steps for the wire-bytes legs


def _fleet_replica_main(pipe, env_name, seed, cfg):
    """Spawn-context entry for one bench replica: a full serving plane in
    its OWN process (the scaling leg measures tier throughput — replicas
    sharing the parent's interpreter would share its GIL and measure
    nothing).  Reports the bound port over the pipe, then blocks until
    the parent sends anything (kill-safe: daemon + terminate backstop)."""
    import os as _os

    _os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.serving import ModelRouter, ServingServer

    env = make_env({"env": env_name})
    module = env.net()
    env.reset()
    obs = env.observation(env.players()[0])
    # seeded init: every replica builds IDENTICAL params, so balanced /
    # re-routed traffic is bit-comparable without shipping weights around
    params = init_variables(module, env, seed=seed)["params"]
    router = ModelRouter(module, obs, cfg, model_dir=".")
    router.publish(1, params)
    server = ServingServer(router, cfg).run()
    pipe.send(server.bound_port)
    try:
        pipe.recv()
    except EOFError:
        pass
    server.shutdown()


def _fleet_router_main(pipe, fleet_cfg):
    """Spawn-context entry for the fleet router front: its own process,
    like every other tier component — the scaling leg is only a
    measurement of the REPLICAS if the router's frame proxying does not
    share an interpreter (a GIL) with the load generators."""
    import os as _os

    _os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from handyrl_tpu.fleet import FleetRouter

    fleet = FleetRouter(fleet_cfg).run(connect_timeout=600.0)
    pipe.send(fleet.bound_port)
    try:
        pipe.recv()
    except EOFError:
        pass
    fleet.shutdown()


def _fleet_load_main(pipe, port, env_name, dur, sessions, collect_models):
    """Spawn-context entry for one load generator: one connection driving
    ``sessions`` server-resident sessions, each with its one in-order
    request outstanding (a session's steps are serial by definition —
    concurrency comes from session count).  Handshakes ready/go over the
    pipe so every generator's window opens together, then reports its
    own counts and elapsed."""
    import os as _os

    _os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import time as _time

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.serving import ServingClient

    env = make_env({"env": env_name})
    env.reset()
    obs = env.observation(env.players()[0])
    client = ServingClient("127.0.0.1", port)
    ok = err = 0
    models = set()
    try:
        sids = [client.open_session() for _ in range(sessions)]
        inflight = [(sid, client.submit(obs, sid=sid)) for sid in sids]
        pipe.send("ready")
        pipe.recv()
        t0 = _time.perf_counter()
        end = t0 + dur
        while _time.perf_counter() < end:
            sid, fut = inflight.pop(0)
            try:
                reply = fut.result(timeout=120)
                ok += 1
                if collect_models:
                    models.add(reply["model"])
            except Exception:
                err += 1
            inflight.append((sid, client.submit(obs, sid=sid)))
        for _sid, fut in inflight:
            try:
                reply = fut.result(timeout=120)
                ok += 1
                if collect_models:
                    models.add(reply["model"])
            except Exception:
                err += 1
        elapsed = _time.perf_counter() - t0
        for sid in sids:
            client.close_session(sid)
        pipe.send({"ok": ok, "err": err, "elapsed": elapsed,
                   "models": sorted(models)})
    finally:
        client.close()


def _fleet_bench(duration: float):
    """Fleet-tier bench over real processes and sockets: saturation QPS
    through the router with one vs two replica processes (the tier must
    SCALE, not just route), a fleet-wide hot-swap under load with a
    zero-drop count, and the session leg's wire-bytes ratio vs
    ship-hidden-state with bit-identical outputs (the session cache must
    be a pure wire optimization, not a numerics change).

    Every tier component runs in its OWN spawn process — N replicas, the
    router, and each load generator — so the replicas are the measured
    bottleneck and the scaling leg reflects tier capacity, not the bench
    parent's GIL.  The leg is still physics-bound by the host: on a
    single-core box two replicas CANNOT beat one (``cores`` lands in the
    result so captures are interpreted against the hardware)."""
    import multiprocessing as _mp
    import threading as _threading

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.serving import ServingClient

    # Geister: the DRC ConvLSTM policy — per-step recurrent state (~27 KB)
    # dwarfs the observation (~1 KB), which is the whole case for server-
    # resident sessions; its compute is heavy enough that the replicas,
    # not the router's Python front, are the tier's bottleneck
    env = make_env({"env": "Geister"})
    module = env.net()
    env.reset()
    obs = env.observation(env.players()[0])
    p2 = init_variables(module, env, seed=2)["params"]
    hidden0 = module.initial_state(())  # the same zeros a fresh session gets

    replica_cfg = {
        "port": 0, "max_models": 4, "slo_ms": 1000.0, "shed_policy": "none",
        "max_batch": 32, "max_wait_ms": 1.0,
        # all reachable buckets pre-warmed (startup AND the swap standby):
        # the zero-drop leg must never pay a hot-path compile
        "warm_buckets": [1, 2, 4, 8, 16, 32],
        "queue_bound": 8192, "recv_timeout": 0.0, "watch_interval": 0.0,
        "stats_interval": 0.0, "session_capacity": 4096, "session_spill": 4096,
    }
    fleet_cfg = {
        "port": 0, "stats_poll_s": 0.5, "replica_stall_s": 60.0,
        "rejoin_backoff_s": 0.5, "rejoin_backoff_max_s": 5.0,
        "stats_interval": 0.0,
    }

    ctx = _mp.get_context("spawn")  # kill-safe: no forked jax runtime state
    procs = []

    def start(target, *args):
        parent, child = ctx.Pipe()
        proc = ctx.Process(target=target, args=(child,) + args, daemon=True)
        proc.start()
        procs.append((proc, parent))
        return proc, parent

    def start_replica():
        _proc, parent = start(_fleet_replica_main, "Geister", 1, replica_cfg)
        if not parent.poll(600):
            raise RuntimeError("fleet bench replica never reported its port")
        return parent.recv()

    def start_router(ports):
        cfg = dict(fleet_cfg, replicas=[f"127.0.0.1:{p}" for p in ports])
        _proc, parent = start(_fleet_router_main, cfg)
        if not parent.poll(600):
            raise RuntimeError("fleet bench router never reported its port")
        return parent.recv(), parent

    def run_load(port, dur, n_clients, collect_models=False, on_go=None):
        gens = [
            start(_fleet_load_main, port, "Geister", dur, FLEET_SESSIONS,
                  collect_models)
            for _ in range(n_clients)
        ]
        # two-phase start: every generator opens its sessions and primes
        # its window FIRST, then all windows open together on "go" — the
        # measured interval never includes a generator's jax import
        for _proc, parent in gens:
            if not parent.poll(600):
                raise RuntimeError("fleet bench load generator never primed")
            parent.recv()
        for _proc, parent in gens:
            parent.send("go")
        if on_go is not None:
            on_go()
        results = []
        for proc, parent in gens:
            if not parent.poll(dur + 600):
                raise RuntimeError("fleet bench load generator hung")
            results.append(parent.recv())
            proc.join(timeout=60)
        ok = sum(r["ok"] for r in results)
        err = sum(r["err"] for r in results)
        elapsed = max(r["elapsed"] for r in results)
        models = set().union(*(set(r["models"]) for r in results))
        return ok / max(elapsed, 1e-6), ok, err, models

    out = {"clients": FLEET_CLIENTS, "sessions": FLEET_SESSIONS,
           # the scaling leg is physics-bound by the host: on one core two
           # replica processes cannot beat one, so captures carry the count
           "cores": os.cpu_count()}
    try:
        # -- one replica up; router (own process) over it ------------------
        port_a = start_replica()
        r1_port, r1_pipe = start_router([port_a])

        # -- wire-bytes leg: ship-state vs session, serial, bit-compared ---
        client = ServingClient("127.0.0.1", r1_port)
        try:
            import numpy as _np

            from handyrl_tpu.utils import tree_map as _tree_map

            hidden = _tree_map(_np.asarray, hidden0)
            shipped = []
            b_sent, b_recv = client.wire_bytes()
            for _ in range(FLEET_RATIO_STEPS):
                reply = client.infer(obs, hidden=hidden, timeout=300)
                hidden = reply["out"].pop("hidden")
                shipped.append(reply["out"])
            ship_bytes = sum(
                a - b for a, b in zip(client.wire_bytes(), (b_sent, b_recv))
            )
            sid = client.open_session()
            b_sent, b_recv = client.wire_bytes()
            sessioned = []
            for _ in range(FLEET_RATIO_STEPS):
                reply = client.infer(obs, sid=sid, timeout=300)
                sessioned.append(reply["out"])
            sess_bytes = sum(
                a - b for a, b in zip(client.wire_bytes(), (b_sent, b_recv))
            )
            client.close_session(sid)
            bitident = all(
                set(a) == set(b) and all(
                    _np.array_equal(_np.asarray(a[k]), _np.asarray(b[k]))
                    for k in a
                )
                for a, b in zip(shipped, sessioned)
            )
            out["session_wire_ratio"] = ship_bytes / max(sess_bytes, 1)
            out["session_bitident"] = bitident
            out["ship_bytes_per_req"] = ship_bytes // FLEET_RATIO_STEPS
            out["session_bytes_per_req"] = sess_bytes // FLEET_RATIO_STEPS
        finally:
            client.close()

        # -- saturation through the router, 1 replica ----------------------
        qps_1, ok_1, err_1, _ = run_load(r1_port, duration, FLEET_CLIENTS)
        out["qps_1"] = qps_1
        out["requests_1"] = ok_1
        out["load_errors"] = err_1
        try:
            r1_pipe.send("stop")
        except (BrokenPipeError, OSError):
            pass

        # -- second replica; same load through a 2-replica tier ------------
        port_b = start_replica()
        r2_port, _r2_pipe = start_router([port_a, port_b])
        qps_2, ok_2, err_2, _ = run_load(r2_port, duration, FLEET_CLIENTS)
        out["qps_2"] = qps_2
        out["requests_2"] = ok_2
        out["load_errors"] += err_2
        out["scaling_x"] = qps_2 / max(qps_1, 1e-6)

        # -- fleet-wide hot-swap under session load: zero drops ------------
        swap_holder = {}

        def do_swap():
            admin = ServingClient("127.0.0.1", r2_port)
            try:
                time.sleep(min(1.0, duration / 4))
                swap_holder["reply"] = admin.swap(2, params=p2, timeout=600)
            finally:
                admin.close()

        # armed by run_load the moment every generator's window opens —
        # started any earlier, the flip could land before the first
        # pre-swap reply and the {1, 2} observation would be vacuous
        swap_thread = _threading.Thread(target=do_swap, daemon=True)
        _qps, ok_s, err_s, models = run_load(
            r2_port, max(duration / 2, 2.0) + 2.0, FLEET_CLIENTS,
            collect_models=True, on_go=swap_thread.start,
        )
        swap_thread.join(600)
        swap = swap_holder.get("reply") or {}
        out["swap_warm_ms"] = swap.get("warm_ms")
        out["swap_replicas"] = swap.get("replicas")
        out["swap_dropped"] = err_s
        out["swap_flip_observed"] = models == {1, 2}

        # -- elastic leg (docs/serving.md §Elastic fleet): a request storm
        # -- scales the fleet up WITHOUT shedding (warm-then-admit), then
        # -- calm scales it back down through the zero-loss migration path
        import shutil as _shutil
        import tempfile as _tempfile

        from handyrl_tpu.config import normalize_args as _normalize
        from handyrl_tpu.fleet import FleetRouter as _FleetRouter
        from handyrl_tpu.fleet.autoscale import ProcessReplicaFactory

        el_dir = _tempfile.mkdtemp(prefix="bench_fleet_elastic_")
        el_args = _normalize({
            "env_args": {"env": "Geister"},
            "train_args": {
                "model_dir": el_dir,
                # max_batch 1 keeps queue depth visible to the autoscaler's
                # polls, so the storm reliably crosses depth_high
                "serving": dict(replica_cfg, max_batch=1, max_wait_ms=0.0,
                                warm_buckets=[1]),
            },
        })
        el_factory = ProcessReplicaFactory(el_args, spawn_timeout_s=600.0)
        el_fleet = _FleetRouter(
            {
                "port": 0, "replicas": [], "stats_poll_s": 0.1,
                "replica_stall_s": 60.0, "rejoin_backoff_s": 0.5,
                "rejoin_backoff_max_s": 5.0, "stats_interval": 0.0,
                "autoscale": {
                    "enabled": True, "min_replicas": 1, "max_replicas": 2,
                    "interval_s": 0.1, "shed_slo": 0.01, "depth_high": 2.0,
                    "depth_low": 1.0, "scale_down_after_s": 1.0,
                    "cooldown_s": 0.5, "warm_timeout_s": 600.0,
                },
            },
            replica_factory=el_factory,
        ).run(connect_timeout=600.0)
        stop_storm = _threading.Event()
        storm_errors = []
        storm_ok = [0]

        def _storm():
            c = ServingClient("127.0.0.1", el_fleet.bound_port)
            try:
                while not stop_storm.is_set():
                    try:
                        c.infer(obs, timeout=300)
                        storm_ok[0] += 1
                    except Exception as exc:
                        storm_errors.append(repr(exc))
                        return
            finally:
                c.close()

        try:
            storm_threads = [
                _threading.Thread(target=_storm, daemon=True)
                for _ in range(8)
            ]
            for t in storm_threads:
                t.start()
            deadline = time.monotonic() + 600.0
            while time.monotonic() < deadline:
                warm = sum(1 for r in el_fleet._reps()
                           if r.alive and r.admitted)
                if el_fleet.scale_ups >= 1 and warm >= 2:
                    break
                time.sleep(0.05)
            stop_storm.set()
            for t in storm_threads:
                t.join(timeout=300)
            admin = ServingClient("127.0.0.1", el_fleet.bound_port)
            try:
                stats = admin.stats()
                shed = sum(r.get("serve_shed") or 0
                           for r in stats["replicas"].values())
                # a session pinned to the newest spawned replica — the
                # calm scale-down must MIGRATE it, not lose it
                victim = [r for r in el_fleet._reps() if r.spawned][-1]
                sid = None
                for _ in range(8):
                    s = admin.open_session()
                    if el_fleet._affinity[s] is victim:
                        sid = s
                        break
                if sid is not None:
                    admin.infer(obs, sid=sid, timeout=300)
                deadline = time.monotonic() + 600.0
                while (el_fleet.scale_downs < 1
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                migrated_ok = (
                    sid is not None
                    and admin.infer(obs, sid=sid, timeout=300) is not None
                )
            finally:
                admin.close()
            out["elastic_scale_ups"] = el_fleet.scale_ups
            out["elastic_scale_downs"] = el_fleet.scale_downs
            out["elastic_storm_requests"] = storm_ok[0]
            out["elastic_storm_errors"] = len(storm_errors)
            out["elastic_scaleup_shed"] = shed
            out["elastic_sessions_migrated"] = el_fleet.sessions_migrated
            out["elastic_handoff_ms"] = round(el_fleet.last_migration_ms, 2)
            out["elastic_migrated_session_ok"] = migrated_ok
        finally:
            stop_storm.set()
            el_fleet.shutdown()
            el_factory.close()
            _shutil.rmtree(el_dir, ignore_errors=True)
    finally:
        for proc, parent in procs:
            try:
                parent.send("stop")
            except (BrokenPipeError, OSError):
                pass
        for proc, _parent in procs:
            proc.join(timeout=30)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=10)
    return out


# league-stage geometry: the training leg is EPOCH-bounded (the gate
# needs whole epoch boundaries, not a wall-clock window)
LEAGUE_EPOCHS = 3 if QUICK else 5
LEAGUE_UPDATE_EPISODES = 16 if QUICK else 24


def _league_bench(duration: float):
    """League plane + autovec stage (docs/league.md §Bench + CI).

    Leg A — the twin-less env compiler's cost, apples to apples: device
    self-play throughput of autovec-lifted TicTacToe vs the hand-written
    VectorTicTacToe (same game, same net, same device set — the per-chip
    frac isolates the lift), judged at ROADMAP item 4's >= 0.5 bar; plus
    lifted ConnectFour absolute throughput (an env with NO hand twin).

    Leg B — a small end-to-end league run (TicTacToe, anchor-seeded):
    PFSP matchmaking, payoff coverage, promotion gate, Elo spread — the
    same path tests/test_league.py::test_league_end_to_end pins, here
    with its realized numbers committed to the bench record.
    """
    import shutil
    import tempfile

    import jax

    from examples.connect_four import ConnectFourRules
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.envs.autovec import autovectorize
    from handyrl_tpu.envs.tictactoe import TicTacToeRules
    from handyrl_tpu.envs.vector_tictactoe import VectorTicTacToe
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.runtime.device_rollout import build_selfplay_fn

    n_games = 2048 if jax.default_backend() == "tpu" else 512

    def selfplay_rate(env_name, venv, window):
        env = make_env({"env": env_name})
        module = env.net()
        params = init_variables(module, env)["params"]
        fn = build_selfplay_fn(venv, module, n_games)
        holder = {"key": jax.random.PRNGKey(0)}

        def call():
            holder["key"], sub = jax.random.split(holder["key"])
            cols = fn(params, sub)
            holder["last"] = cols
            return cols["alive"]

        calls_per_sec = _timed_loop(call, window)
        alive = float(jax.device_get(holder["last"]["alive"]).sum())
        return calls_per_sec * alive

    window = max(duration / 4, 2.0)
    hand = selfplay_rate("TicTacToe", VectorTicTacToe, window)
    auto = selfplay_rate("TicTacToe", autovectorize(TicTacToeRules), window)
    c4 = selfplay_rate("ConnectFour", autovectorize(ConnectFourRules), window)
    out = {
        "twin_steps_per_sec": hand,
        "autovec_steps_per_sec": auto,
        # identical device sets on both sides, so the ratio IS per-chip
        "autovec_per_chip_frac": auto / max(hand, 1e-9),
        "autovec_target_met": auto / max(hand, 1e-9) >= 0.5,
        "connectfour_autovec_steps_per_sec": c4,
        "n_games": n_games,
    }

    # -- leg B: end-to-end league run ---------------------------------------
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.league.learner import LeagueLearner

    run_dir = tempfile.mkdtemp(prefix="bench_league_")
    try:
        cfg = normalize_args({
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 4,
                "update_episodes": LEAGUE_UPDATE_EPISODES,
                "minimum_episodes": 12,
                "maximum_episodes": 500,
                "num_batchers": 0,
                "batch_pipeline": "thread",
                "epochs": LEAGUE_EPOCHS,
                "eval_rate": 0.0,
                "worker": {"num_parallel": 2},
                "metrics_path": os.path.join(run_dir, "metrics.jsonl"),
                "model_dir": os.path.join(run_dir, "models"),
                # the bar below random-vs-random wp: the bench commits the
                # MECHANICS' numbers (coverage, spread, promotions) —
                # candidate strength vs a real bar is a soak concern
                "league": {"promote_winrate": 0.4, "promote_games": 3,
                           "selfplay_rate": 0.15},
            },
        })
        t0 = time.perf_counter()
        learner = LeagueLearner(cfg)
        rc = learner.run()
        out["run_seconds"] = time.perf_counter() - t0
        if rc != 0:
            raise RuntimeError(f"league run exited {rc}")
        from handyrl_tpu.league import ANCHOR, CANDIDATE
        from handyrl_tpu.utils.metrics import read_metrics

        payoff = learner.league.payoff
        pool = [m.name for m in learner.league.opponent_pool()]
        rated = payoff.elo(pool + [CANDIDATE], anchor=ANCHOR)
        out["population"] = len(learner.league.members)
        out["promotions"] = learner.league.promotions
        out["matches"] = payoff.matches
        # a promotion hands the candidate's books to the frozen member, so
        # the FINAL row can legitimately read 0; the coverage story is the
        # best fill any generation reached (1.0 = some gate saw every pair)
        records = read_metrics(cfg["train_args"]["metrics_path"])
        out["payoff_coverage"] = max(
            (r.get("league_payoff_coverage") or 0.0 for r in records),
            default=0.0,
        )
        out["elo_spread"] = (
            max(rated.values()) - min(rated.values()) if len(rated) >= 2 else None
        )
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    return out


def _lowprec_bench(duration: float):
    """Low-precision fast path (docs/performance.md §Low-precision): both
    precision rungs MEASURED in one session so the ratios divide out
    run-to-run variance.

    Weight rung: resident param bytes fp32 vs int8 (models/quantize.py
    per-channel symmetric), engine inference rate per rung through the
    same jitted-apply path the serving plane dispatches, and the
    publish-time calibration record (measured output deviation over
    replay obs).  Obs rung: identical seeded self-play encoded fp32 vs
    int8 — raw obs bytes moved and compressed wire bytes — plus train
    updates/s consuming each encoding (int8 windows dequantize inside
    the jitted sample/forward programs).  Parity is MEASURED, never
    assumed: a short-trained policy pits its int8 engine against its
    fp32 engine seat-balanced through the league's PayoffMatrix ledger
    (|wp - 0.5| <= 0.03 over >= 400 games; QUICK mode plays 40 — enough
    to exercise the verdict path, not to bank it).  On CPU the byte
    ratios are exact and portable; the rates are proxy numbers (no MXU,
    no HBM) — BENCH_r06 TPU capture instructions in docs/performance.md."""
    import random as _random

    import jax
    import numpy as np

    from handyrl_tpu.agents import Agent
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.league.matchmaker import PayoffMatrix
    from handyrl_tpu.models import build_inference_model
    from handyrl_tpu.models.quantize import (
        calibration_batches_from_store, calibration_report, obs_quant_spec,
        param_bytes, quantize_params,
    )
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime.evaluation import evaluate_mp
    from handyrl_tpu.runtime.replay import decompress_block
    from handyrl_tpu.utils import tree_map

    out = {"backend": jax.default_backend()}
    fill = 12 if QUICK else 32

    # -- weight rung ------------------------------------------------------
    args = _make_args("TicTacToe", {"batch_size": 32, "forward_steps": 8})
    _random.seed(1009)
    env, module, model, store = _fill_store(args, fill)
    params = model.variables["params"]
    out["weight_bytes_fp32"] = param_bytes(params)
    out["weight_bytes_int8"] = param_bytes(quantize_params(params))
    out["weight_bytes_ratio"] = out["weight_bytes_fp32"] / out["weight_bytes_int8"]

    env.reset()
    obs = env.observation(env.players()[0])
    B = 64
    obs_b = tree_map(lambda x: np.broadcast_to(np.asarray(x)[None],
                                               (B,) + np.asarray(x).shape).copy(),
                     obs)
    for rung, dtype in (("fp32", "float32"), ("int8", "int8")):
        eng = build_inference_model(module, params, dtype)
        hidden = eng.init_hidden((B,))
        rate = _timed_loop(
            lambda: eng.inference_batch_async(obs_b, hidden), duration / 4
        )
        out[f"infer_qps_{rung}"] = rate * B
    out["infer_int8_vs_fp32"] = out["infer_qps_int8"] / out["infer_qps_fp32"]

    calib = calibration_report(
        module, params, calibration_batches_from_store(store, 4)
    )
    out["calib_batches"] = calib["calib_batches"]
    out["calib_max_dev"] = calib["calib_max_dev"]
    out["calib_mean_dev"] = calib["calib_mean_dev"]

    # -- obs rung: identical seeded self-play, fp32 vs int8 encoding ------
    train_ups = {}
    obs_bytes = {}
    wire_bytes = {}
    ctx_f = state_f = None
    for rung, flag in (("fp32", False), ("int8", True)):
        targs = _make_args("TicTacToe", {"batch_size": 32, "forward_steps": 8,
                                         "obs_int8": flag})
        _random.seed(123)  # SAME trajectories both rungs: only encoding differs
        _, mod2, model2, store2 = _fill_store(targs, fill)
        raw = blob = 0
        for ep in store2.snapshot():
            blob += sum(len(b) for b in ep["blocks"])
            for b in ep["blocks"]:
                raw += sum(
                    leaf.nbytes
                    for leaf in jax.tree.leaves(decompress_block(b)["obs"])
                )
        obs_bytes[rung], wire_bytes[rung] = raw, blob
        if flag:
            targs["_obs_quant"] = obs_quant_spec(make_env(targs["env"]))
        ctx = TrainContext(mod2, targs, make_mesh(targs["mesh"]))
        state = ctx.init_state(model2.variables["params"])
        batches = [ctx.put_batch(_sample_batch(store2, targs)) for _ in range(4)]
        holder = {"state": state, "i": 0}

        def step():
            holder["state"], metrics = ctx.train_step(
                holder["state"], batches[holder["i"] % 4], 1e-3
            )
            holder["i"] += 1
            return metrics["total"]

        train_ups[rung] = _timed_loop(step, duration / 4)
        if not flag:
            ctx_f, state_f, batches_f, holder_f = ctx, state, batches, holder
    out["obs_bytes_fp32"], out["obs_bytes_int8"] = obs_bytes["fp32"], obs_bytes["int8"]
    out["obs_bytes_ratio"] = obs_bytes["fp32"] / obs_bytes["int8"]
    out["wire_bytes_ratio"] = wire_bytes["fp32"] / wire_bytes["int8"]
    out["train_updates_per_sec_fp32"] = train_ups["fp32"]
    out["train_updates_per_sec_int8"] = train_ups["int8"]
    out["train_int8_vs_fp32"] = train_ups["int8"] / train_ups["fp32"]

    # -- wp parity: int8 engine vs fp32 engine, SAME short-trained params --
    # (a uniform random policy would make any parity bar vacuous, so keep
    # training the fp32 context briefly before extracting the params)
    t_end = time.perf_counter() + min(duration, 12.0)
    while time.perf_counter() < t_end:
        holder_f["state"], m = ctx_f.train_step(
            holder_f["state"], batches_f[holder_f["i"] % 4], 1e-3
        )
        holder_f["i"] += 1
    jax.block_until_ready(m["total"])
    trained = tree_map(np.asarray, jax.device_get(holder_f["state"]["params"]))

    games = 40 if QUICK else 400
    a_q = Agent(build_inference_model(module, trained, "int8"),
                temperature=1.0, seed=11)
    a_f = Agent(build_inference_model(module, trained, "float32"),
                temperature=1.0, seed=12)
    results = evaluate_mp({"env": "TicTacToe"}, {0: a_q, 1: a_f},
                          games, num_workers=2)
    payoff = PayoffMatrix()
    for _pat, res in results.items():
        for outcome, count in res.items():
            payoff.record_score("int8", "fp32", float(outcome),
                                -float(outcome), n=count)
    wp = payoff.win_points("int8", "fp32")
    out["wp"] = wp
    out["wp_games"] = payoff.games("int8", "fp32")
    out["wp_delta"] = abs(wp - 0.5)
    out["wp_parity_target_met"] = out["wp_delta"] <= 0.03
    return out


def _flywheel_bench(duration: float):
    """Data-flywheel bench (docs/serving.md §Data flywheel) over the REAL
    framed-socket transport: harvest assembly rate (scripted clients play
    full games through per-player sessions and close each step over the
    harvest protocol), ingest drain rate in wire bytes/s, and the quality
    plane's two latencies — snapshot-available -> gated promotion flip,
    and first bad outcome -> sentinel demote-to-incumbent."""
    import random as _random
    import tempfile

    import numpy as np

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.flywheel import FlywheelPlane
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.runtime.checkpoint import save_epoch_snapshot
    from handyrl_tpu.serving import ModelRouter, ServingClient, ServingServer

    env = make_env({"env": "TicTacToe"})
    module = env.net()
    env.reset()
    obs0 = env.observation(0)
    p1 = init_variables(module, env, seed=1)["params"]
    p2 = init_variables(module, env, seed=2)["params"]

    model_dir = tempfile.mkdtemp(prefix="bench_flywheel_")
    save_epoch_snapshot(model_dir, 1, p1, {"bench": 0}, 0)

    promote_games = 8
    quality_window = 4
    fly_cfg = {
        "enabled": True, "gate_promotions": True, "promote_winrate": 0.55,
        "promote_games": promote_games, "quality_window": quality_window,
        "demote_drop": 0.1, "shadow_fraction": 0.0,
        "harvest_max_open": 512, "harvest_ttl_s": 600.0,
    }
    gen_args = {"gamma": 0.8, "compress_steps": 8, "observation": True,
                "obs_int8": False}
    cfg = {
        "port": 0, "max_models": 4, "slo_ms": 1000.0, "shed_policy": "none",
        "max_batch": 64, "max_wait_ms": 1.0,
        "warm_buckets": [1, 2, 4, 8, 16],
        "queue_bound": 8192, "recv_timeout": 0.0, "watch_interval": 0.2,
        "stats_interval": 0.0,
    }
    router = ModelRouter(module, obs0, cfg, model_dir=model_dir)
    router.publish(1, p1)
    flywheel = FlywheelPlane(router, model_dir, fly_cfg, gen_args)
    server = ServingServer(router, cfg, flywheel=flywheel).run()
    out = {}
    client = ServingClient("127.0.0.1", server.bound_port)
    try:
        players = env.players()

        def play_one():
            """One full game over the wire: per-player sessions bound into
            a harvest episode, policies sampled from the served replies."""
            sids = [client.open_session() for _ in players]
            hid = client.harvest_open(players, sids)
            env.reset()
            while not env.terminal():
                turn_players = env.turns()
                actions = [None] * len(players)
                legal_lists = [None] * len(players)
                moves = {}
                for p in turn_players:
                    j = players.index(p)
                    reply = client.infer(env.observation(p), sid=sids[j])
                    logits = np.asarray(reply["out"]["policy"]).reshape(-1)
                    legal = env.legal_actions(p)
                    action = max(legal, key=lambda a: (logits[a], _random.random()))
                    actions[j] = int(action)
                    legal_lists[j] = list(legal)
                    moves[p] = int(action)
                turn = turn_players[0] if turn_players else None
                env.step(moves)
                reward = env.reward()
                rewards = [reward.get(p) for p in players]
                client.harvest_step(hid, actions, legal_lists, rewards, turn)
            outcome = env.outcome()
            kept = client.harvest_close(hid, [outcome.get(p, 0.0) for p in players])
            for sid in sids:
                client.close_session(sid)
            return kept

        # -- phase 1: harvest assembly over the wire ----------------------
        episodes = 0
        t0 = time.perf_counter()
        end = t0 + duration
        while time.perf_counter() < end:
            if play_one():
                episodes += 1
        harvest_s = time.perf_counter() - t0
        out["episodes"] = episodes
        out["harvest_eps_per_sec"] = episodes / max(harvest_s, 1e-6)

        # -- phase 2: ingest drain rate (the learner poll's wire cost) ----
        _sent0, recv0 = client.wire_bytes()
        pulled = 0
        t0 = time.perf_counter()
        while True:
            eps, counts = client.harvest_pull(max_episodes=64)
            pulled += len(eps)
            if not eps:
                break
        pull_s = time.perf_counter() - t0
        _sent1, recv1 = client.wire_bytes()
        out["pull_episodes"] = pulled
        out["ingest_bytes_per_sec"] = (recv1 - recv0) / max(pull_s, 1e-6)
        out["dropped"] = (counts.get("flywheel_dropped_malformed", 0)
                          + counts.get("flywheel_dropped_truncated", 0))

        def wait_for(pred, timeout=30.0):
            t = time.perf_counter()
            while time.perf_counter() - t < timeout:
                if pred():
                    return True
                time.sleep(0.02)
            return False

        # -- phase 3: gated promotion latency -----------------------------
        # snapshot 2 lands -> watch loop stages it -> live wins clear the
        # gate -> latest flips.  The measured span is the whole mechanism
        t0 = time.perf_counter()
        save_epoch_snapshot(model_dir, 2, p2, {"bench": 0}, 0)
        staged = wait_for(lambda: router.candidate_id() == 2)
        if staged:
            for _ in range(promote_games):
                client.report_outcome(2, 1.0)
        promoted = wait_for(lambda: router.latest_id() == 2)
        out["promote_latency_ms"] = (time.perf_counter() - t0) * 1000.0
        out["promote_observed"] = promoted

        # -- phase 4: sentinel demote latency -----------------------------
        # the promoted snapshot turns bad live: losses past the window
        # drag its EMA under the bar and the sentinel restores epoch 1
        t0 = time.perf_counter()
        demoted = False
        if promoted:
            for _ in range(quality_window * 2):
                client.report_outcome(2, -1.0)
            demoted = wait_for(lambda: router.latest_id() == 1)
        out["demote_ms"] = (time.perf_counter() - t0) * 1000.0
        out["demote_observed"] = demoted

        q = flywheel.stats_record()
        out["promotions"] = q.get("quality_promotions", 0)
        out["demotions"] = q.get("quality_demotions", 0)
        out["games"] = q.get("quality_games", 0)
    finally:
        client.close()
        server.shutdown()
    return out


KNOWN_STAGES = (
    "tictactoe", "device-selfplay", "geese-device-selfplay", "geese-gen",
    "geese-train", "northstar", "northstar2", "northstar3", "northstar3mp",
    "northstar4",
    "geese-bf16", "geister", "geister-device-selfplay", "geister-devreplay",
    "serving", "fleet", "league", "lowprec", "flywheel", "transformer",
    "transformer_long", "flash",
)
# stages that consume another stage's result (main() gates them on it)
STAGE_DEPS = {
    "northstar": ("geese-train",),
    "northstar2": ("geese-train",),
    "northstar3": ("geese-train",),
    "northstar4": ("geese-train",),
    "geese-bf16": ("geese-train",),
}


def _stage_filter() -> Optional[set]:
    """``BENCH_STAGES=a,b,c`` limits the run to the named stages (for
    banking one new stage's numbers on a live chip without re-paying the
    full ~25 min suite).  Unset or empty means all stages — an empty
    string from CI interpolation must not skip everything.  Dependencies
    are pulled in automatically (BENCH_STAGES=northstar2 also runs
    geese-train: the northstar/bf16 stages reuse its store + context and
    are gated on its result in main())."""
    raw = os.environ.get("BENCH_STAGES")
    if raw is None or not raw.strip():
        return None
    names = {s.strip() for s in raw.split(",") if s.strip()}
    for n in tuple(names):
        names.update(STAGE_DEPS.get(n, ()))
    return names


def _run_stage(result: dict, name: str, fn, retries: int = 1,
               retry_delay: float = 20.0):
    """Run one bench stage with a single retry.  One transient failure
    must not null a stage's numbers: a failed stage re-runs once after a
    short wait, and the per-stage error lands in
    result["error"] only when every attempt fails.  A failed attempt's
    PARTIAL writes to ``result`` are rolled back (a stage that died after
    recording throughput must not leave numbers that read as measured),
    and every attempt's traceback is kept.  Returns the stage's value, or
    None after final failure."""
    only = _stage_filter()
    if only is not None and name not in only:
        result["extra"].setdefault("stages_skipped", []).append(name)
        return None
    deadline = _deadline_s()
    if deadline > 0:
        remaining = deadline - (time.perf_counter() - _T0)
        if remaining < _env_float("BENCH_STAGE_MIN_S", 60.0):
            # too little runway for a meaningful measurement: finish clean
            # (rc=0, honest note) instead of being SIGKILLed mid-stage
            result["extra"].setdefault("stages_deadline_skipped", []).append(name)
            _note(f"{name}: skipped — {remaining:.0f}s of {deadline:.0f}s "
                  f"deadline left")
            _emit_snapshot(result)
            return None
    errs = []
    for attempt in range(retries + 1):
        snap = {k: result[k] for k in ("value", "vs_baseline", "error")}
        snap_extra = dict(result["extra"])
        try:
            val = fn()
            _emit_snapshot(result)
            return val
        except Exception:
            result.update(snap)
            result["extra"] = snap_extra
            errs.append(f"attempt {attempt + 1}: "
                        + traceback.format_exc(limit=3))
            if attempt < retries:
                _note(f"{name}: attempt {attempt + 1} failed; retrying in "
                      f"{retry_delay:.0f}s")
                time.sleep(retry_delay)
    result["error"] = (result["error"] or "") + f" {name}: " + " | ".join(errs)
    _emit_snapshot(result)
    return None


def main() -> None:
    result = {
        "metric": "tictactoe_trained_env_steps_per_sec",
        "value": None,
        "unit": "env-steps/s",
        "vs_baseline": None,
        "platform": None,
        "error": None,
        "extra": {},
    }

    # a typo'd BENCH_STAGES must not burn chip time on a run that silently
    # skips everything: unknown names fail before any device work
    only = _stage_filter()
    if only and not only.issubset(KNOWN_STAGES):
        raise SystemExit(
            f"unknown BENCH_STAGES name(s) {sorted(only - set(KNOWN_STAGES))}; "
            f"valid: {', '.join(KNOWN_STAGES)}"
        )

    # a stage-filtered run REFRESHES its stages' numbers in place: seed
    # from the existing side file so the skipped stages' banked metrics
    # survive the rewrite (a BENCH_STAGES=northstar3mp smoke must not
    # clobber the full capture tests/test_perfgate.py loads).  Run
    # bookkeeping (stages_skipped, partial) is always THIS run's.
    if only is not None:
        try:
            with open(_snapshot_path()) as f:
                prev = json.loads(f.readline())
            for k, v in (prev.get("extra") or {}).items():
                if k not in ("stages_skipped", "stages_deadline_skipped"):
                    result["extra"][k] = v
            if "tictactoe" not in only:
                result["value"] = prev.get("value")
                result["vs_baseline"] = prev.get("vs_baseline")
        except (OSError, ValueError):
            pass  # no prior snapshot: the filtered run stands alone

    from handyrl_tpu.utils import enable_compile_cache

    enable_compile_cache()
    devices = _accelerator_devices()
    result["platform"] = f"{devices[0].platform}:{devices[0].device_kind} x{len(devices)}"
    # first parseable line lands the moment the devices resolve: even a
    # kill during the headline stage leaves the platform behind
    _emit_snapshot(result)

    peak = _peak_flops(devices[0])
    n_dev = len(devices)

    # 1. headline: TicTacToe train throughput (same metric as round 1)
    def stage_tictactoe():
        ttt = _train_bench("TicTacToe", {}, T_TRAIN, n_dev, fused=True)
        result["value"] = round(ttt["trained_env_steps_per_sec"], 1)
        result["vs_baseline"] = round(
            ttt["trained_env_steps_per_sec"] / REFERENCE_TRAINED_STEPS_PER_SEC, 3
        )
        result["extra"]["tictactoe_updates_per_sec"] = round(ttt["updates_per_sec"], 2)
        if ttt.get("fused_updates_per_sec"):
            result["extra"]["tictactoe_fused_updates_per_sec"] = round(
                ttt["fused_updates_per_sec"], 2
            )
            result["extra"]["tictactoe_fused_env_steps_per_sec"] = round(
                ttt["fused_updates_per_sec"]
                * ttt["args"]["batch_size"] * ttt["args"]["forward_steps"],
                1,
            )
        # MFU at the fastest update rate this model reaches (fused when
        # available); tiny net, so the honest number is tiny — reported
        # anyway (VERDICT r3 item 2: every path states its MFU or why not)
        if ttt["flops_per_step"] and peak:
            ups = ttt.get("fused_updates_per_sec") or ttt["updates_per_sec"]
            result["extra"]["tictactoe_mfu"] = _sig(
                ttt["flops_per_step"] * ups / (peak * n_dev)
            )
        if ttt.get("fused_error"):
            result["error"] = (result["error"] or "") + " ttt-fused: " + ttt["fused_error"]
        return ttt

    _run_stage(result, "tictactoe", stage_tictactoe)

    # 1b. on-device self-play: the zero-host-round-trip actor plane
    def stage_device_selfplay():
        dsp = _device_selfplay_bench(T_GEN / 2)
        result["extra"]["device_selfplay_env_steps_per_sec"] = round(
            dsp["env_steps_per_sec"], 1
        )
        result["extra"]["device_selfplay_vs_reference_gen"] = round(
            dsp["env_steps_per_sec"] / REFERENCE_GEN_STEPS_PER_SEC, 2
        )

    _run_stage(result, "device-selfplay", stage_device_selfplay)

    geese_over = {"turn_based_training": False, "observation": False}

    # 1c. north-star actor plane, on-device: streaming HungryGeese self-play
    def stage_geese_device_selfplay():
        gd = _streaming_selfplay_bench("HungryGeese", geese_over, T_GEN / 2)
        result["extra"]["geese_device_selfplay_env_steps_per_sec"] = round(
            gd["env_steps_per_sec"], 1
        )
        result["extra"]["geese_device_selfplay_player_steps_per_sec"] = round(
            gd["player_steps_per_sec"], 1
        )
        result["extra"]["geese_device_selfplay_episodes_per_sec"] = _sig(
            gd["episodes_per_sec"]
        )
        if gd["episodes_note"]:
            result["extra"]["geese_device_selfplay_episodes_note"] = gd["episodes_note"]
        result["extra"]["geese_device_selfplay_vs_reference_gen"] = round(
            gd["env_steps_per_sec"] / REFERENCE_GEESE_GEN_STEPS_PER_SEC, 2
        )

    _run_stage(result, "geese-device-selfplay", stage_geese_device_selfplay)

    # 2. host actor plane: HungryGeese generation through the engine
    # (32 actors x 4 simultaneous players pre-submit -> deep request queue,
    # so each device round-trip serves a full inference batch even when
    # per-call latency is high)
    def stage_geese_gen():
        gen = _generation_bench("HungryGeese", geese_over, T_GEN, num_actors=32)
        result["extra"]["geese_gen_env_steps_per_sec"] = round(gen["env_steps_per_sec"], 1)
        result["extra"]["geese_gen_vs_reference"] = round(
            gen["env_steps_per_sec"] / REFERENCE_GEESE_GEN_STEPS_PER_SEC, 3
        )
        result["extra"]["geese_gen_mean_infer_batch"] = round(gen["mean_infer_batch"], 1)

    _run_stage(result, "geese-gen", stage_geese_gen)

    # 3. north-star learner plane: GeeseNet train + starvation + MFU
    def stage_geese_train():
        gt = _train_bench("HungryGeese", geese_over, T_TRAIN, n_dev)
        result["extra"]["geese_trained_env_steps_per_sec"] = _sig(
            gt["trained_env_steps_per_sec"], 5
        )
        result["extra"]["geese_updates_per_sec"] = _sig(gt["updates_per_sec"])
        # MFU is ALWAYS reported — as a number, or as null plus the reason
        # (round 2 silently omitted it when the peak-FLOPs lookup missed)
        if gt["flops_per_step"]:
            result["extra"]["geese_flops_per_step"] = gt["flops_per_step"]
            if peak:
                result["extra"]["geese_mfu"] = round(
                    gt["flops_per_step"] * gt["updates_per_sec"] / (peak * n_dev), 4
                )
            else:
                result["extra"]["geese_mfu"] = None
                result["extra"]["geese_mfu_note"] = (
                    "no peak-FLOPs table entry for device kind "
                    f"'{getattr(devices[0], 'device_kind', '?')}'"
                )
        else:
            result["extra"]["geese_mfu"] = None
            result["extra"]["geese_mfu_note"] = (
                "XLA cost analysis returned no flops from either the native "
                "or the CPU-backend lowering, and the analytic jaxpr counter "
                "also came up empty"
            )
        pipe = _pipeline_bench(gt, T_TRAIN)
        result["extra"]["geese_pipeline_updates_per_sec"] = _sig(pipe["updates_per_sec"])
        result["extra"]["geese_input_wait_frac"] = round(pipe["input_wait_frac"], 4)
        # per-stage breakdown (seconds inside the timed window): sample /
        # assemble / free-slot wait / ready wait / device put, plus the
        # mean device-queue depth and which plane ran (shm or thread)
        result["extra"]["geese_pipeline_stages"] = pipe["stages"]
        return gt

    gt = _run_stage(result, "geese-train", stage_geese_train)

    # 3c. the north-star loop itself: device self-play feeding training,
    # concurrently, on the same chip (VERDICT r2 item 2)
    def stage_northstar():
        ns = _concurrent_northstar_bench(gt, T_TRAIN)
        if "skipped" in ns:
            result["extra"]["northstar_note"] = ns["skipped"]
            return
        result["extra"]["northstar_concurrent_trained_env_steps_per_sec"] = _sig(
            ns["trained_env_steps_per_sec"], 5
        )
        result["extra"]["northstar_concurrent_selfplay_env_steps_per_sec"] = _sig(
            ns["selfplay_env_steps_per_sec"], 5
        )
        result["extra"]["northstar_input_wait_frac"] = round(ns["input_wait_frac"], 4)
        result["extra"]["northstar_per_chip_frac"] = _sig(ns["per_chip_northstar_frac"])
        if ns.get("rollout_error"):
            result["error"] = (result["error"] or "") + " northstar-rollout: " + ns["rollout_error"]

    if gt is not None:
        _run_stage(result, "northstar", stage_northstar)

    # 3d. north-star v2: device-resident replay — records ingested into
    # on-device rings, batches sampled + assembled + stepped in ONE
    # dispatch; the data path never touches the host.  Lane/fuse geometry
    # from the round-4 duty-cycle sweep (BASELINE.md): more SGD per
    # rollout call so the chip trains instead of only self-playing.
    def stage_northstar2():
        ns2 = _device_replay_northstar_bench(gt, T_TRAIN)
        if "skipped" in ns2:
            result["extra"]["northstar2_note"] = ns2["skipped"]
            return
        result["extra"]["northstar2_trained_env_steps_per_sec"] = _sig(
            ns2["trained_env_steps_per_sec"], 5
        )
        result["extra"]["northstar2_selfplay_env_steps_per_sec"] = _sig(
            ns2["selfplay_env_steps_per_sec"], 5
        )
        result["extra"]["northstar2_rollout_time_frac"] = round(
            ns2["rollout_time_frac"], 4
        )
        import jax

        if jax.default_backend() != "cpu":
            # the loop no longer host-syncs per rollout (satellite fix:
            # the fused baseline must not be handicapped vs northstar3),
            # so with async dispatch rollout_s is enqueue time only — the
            # duty split is exact on CPU but under-reports here; flag it
            # rather than silently redefining the round-4 headline number
            result["extra"]["northstar2_rollout_time_frac_note"] = (
                "async dispatch: host-side enqueue share, not device duty"
            )
        result["extra"]["northstar2_produce_consume_ratio"] = _sig(
            ns2["produce_consume_ratio"]
        )
        result["extra"]["northstar2_per_chip_frac"] = _sig(
            ns2["per_chip_northstar_frac"]
        )
        # train-plane MFU of the all-on-device loop: same jitted step as
        # stage 3 (same batch geometry), so gt's flops/step applies
        if gt["flops_per_step"] and peak:
            result["extra"]["northstar2_train_mfu"] = _sig(
                gt["flops_per_step"] * ns2["updates_per_sec"] / (peak * n_dev)
            )
        if not ns2["loss_finite"]:
            result["error"] = (result["error"] or "") + " northstar2: non-finite loss"

    if gt is not None:
        _run_stage(result, "northstar2", stage_northstar2)

    # 3e. north-star v3: DISAGGREGATED planes — self-play on an actor
    # mesh, training on a disjoint learner mesh, concurrently (the
    # Podracer/Sebulba split; needs >= 2 devices).  The fused loop's
    # rollout_time_frac 0.91 becomes a chip split here.
    def stage_northstar3():
        ns3 = _split_plane_northstar_bench(gt, T_TRAIN)
        if "skipped" in ns3:
            result["extra"]["northstar3_note"] = ns3["skipped"]
            return
        result["extra"]["northstar3_chips"] = (
            f"{ns3['learner_chips']}L+{ns3['actor_chips']}A"
        )
        result["extra"]["northstar3_trained_env_steps_per_sec"] = _sig(
            ns3["trained_env_steps_per_sec"], 5
        )
        result["extra"]["northstar3_selfplay_env_steps_per_sec"] = _sig(
            ns3["selfplay_env_steps_per_sec"], 5
        )
        result["extra"]["northstar3_selfplay_standalone_env_steps_per_sec"] = _sig(
            ns3["selfplay_standalone_env_steps_per_sec"], 5
        )
        result["extra"]["northstar3_selfplay_concurrent_frac"] = _sig(
            ns3["selfplay_concurrent_frac"]
        )
        result["extra"]["northstar3_rollout_time_frac"] = round(
            ns3["rollout_time_frac"], 4
        )
        result["extra"]["northstar3_learner_train_time_frac"] = round(
            ns3["learner_train_time_frac"], 4
        )
        result["extra"]["northstar3_actor_busy_frac"] = round(
            ns3["actor_busy_frac"], 4
        )
        result["extra"]["northstar3_param_lag_mean"] = _sig(
            ns3["param_lag_mean"]
        )
        result["extra"]["northstar3_xfer_bytes_per_sec"] = _sig(
            ns3["xfer_bytes_per_sec"]
        )
        result["extra"]["northstar3_produce_consume_ratio"] = _sig(
            ns3["produce_consume_ratio"]
        )
        result["extra"]["northstar3_per_chip_frac"] = _sig(
            ns3["per_chip_northstar_frac"]
        )
        if gt["flops_per_step"] and peak:
            # flops_per_step was traced at geese-train's batch size; the
            # split stage may round the batch down to a learner-dp
            # multiple, and update FLOPs scale linearly with batch
            flops = gt["flops_per_step"] * (
                ns3["batch_size"] / gt["args"]["batch_size"]
            )
            result["extra"]["northstar3_train_mfu"] = _sig(
                flops * ns3["updates_per_sec"] / (peak * ns3["learner_chips"])
            )
        if ns3.get("rollout_error"):
            result["error"] = (result["error"] or "") + (
                " northstar3-rollout: " + ns3["rollout_error"]
            )
        if not ns3["loss_finite"]:
            result["error"] = (result["error"] or "") + " northstar3: non-finite loss"

    if gt is not None:
        _run_stage(result, "northstar3", stage_northstar3)

    # 3e'. north-star v3 pod-slice leg: the SAME split plane across TWO
    # OS processes under jax.distributed (subprocess children, CPU-forced
    # 4+4 virtual devices — measures the pod-slice topology's mechanics,
    # not chip throughput; no geese-train dependency, the children build
    # their own ParallelTicTacToe run)
    def stage_northstar3mp():
        mp = _multiprocess_split_plane_bench(epochs=2 if QUICK else 3)
        if "skipped" in mp:
            result["extra"]["northstar3mp_note"] = mp["skipped"]
            return
        result["extra"]["northstar3mp_processes"] = mp["processes"]
        result["extra"]["northstar3mp_updates_per_sec"] = _sig(
            mp["updates_per_sec"]
        )
        result["extra"]["northstar3mp_trained_env_steps_per_sec"] = _sig(
            mp["trained_env_steps_per_sec"], 5
        )
        result["extra"]["northstar3mp_episodes_per_sec"] = _sig(
            mp["episodes_per_sec"]
        )
        result["extra"]["northstar3mp_actor_busy_frac"] = round(
            mp["actor_busy_frac"], 4
        )
        result["extra"]["northstar3mp_xfer_bytes_per_sec"] = _sig(
            mp["xfer_bytes_per_sec"]
        )
        if not mp["both_planes_concurrent"]:
            result["error"] = (result["error"] or "") + (
                " northstar3mp: no epoch with both planes' rates nonzero"
            )

    _run_stage(result, "northstar3mp", stage_northstar3mp)

    # 3f. north-star v4: the host-pipeline scaling curve (shm plane at
    # 1/2/4 batcher processes) + the host-bypass device stage, all fed
    # from geese-train's store, each judged against the direct updates/s
    # (ROADMAP item 3: host-fed >= 50% of direct at input_wait < 0.05)
    def stage_northstar4():
        ns4 = _pipeline_scaling_bench(gt, T_TRAIN)
        for name, p in ns4["points"].items():
            result["extra"][f"northstar4_{name}_updates_per_sec"] = _sig(
                p["updates_per_sec"]
            )
            result["extra"][f"northstar4_{name}_input_wait_frac"] = round(
                p["input_wait_frac"], 4
            )
            result["extra"][f"northstar4_{name}_mode"] = p["mode"]
            result["extra"][f"northstar4_{name}_stages"] = p["stages"]
        result["extra"]["northstar4_direct_updates_per_sec"] = _sig(
            ns4["direct_updates_per_sec"]
        )
        result["extra"]["northstar4_best_host"] = ns4["best_host"]
        result["extra"]["northstar4_best_host_vs_direct"] = _sig(
            ns4["best_host_vs_direct"]
        )
        result["extra"]["northstar4_device_vs_direct"] = _sig(
            ns4["device_vs_direct"]
        )
        result["extra"]["northstar4_host_target_met"] = ns4["host_target_met"]
        result["extra"]["northstar4_device_target_met"] = ns4["device_target_met"]

    if gt is not None:
        _run_stage(result, "northstar4", stage_northstar4)

    # 3b. bf16 mixed precision (MXU-rate forward/backward, fp32 master
    # weights) on the same store — the compute_dtype knob's headroom
    def stage_geese_bf16():
        gt16 = _train_bench(
            "HungryGeese", {**geese_over, "compute_dtype": "bfloat16"},
            T_TRAIN, n_dev, reuse=gt,
        )
        result["extra"]["geese_bf16_updates_per_sec"] = _sig(gt16["updates_per_sec"])

    if gt is not None:
        _run_stage(result, "geese-bf16", stage_geese_bf16)

    # 4. recurrent path: Geister DRC ConvLSTM with burn-in + UPGO — the
    # long-horizon imperfect-info config (BASELINE.json configs[3]); the
    # train step here is a T-step lax.scan with masked hidden carry
    def stage_geister():
        geister = _train_bench(
            "Geister",
            {"burn_in_steps": 8, "forward_steps": 16, "observation": True,
             "policy_target": "UPGO", "value_target": "UPGO"},
            T_TRAIN,
            n_dev,
            fill_episodes=12,  # 200-turn episodes; filling dominates otherwise
        )
        result["extra"]["geister_rnn_updates_per_sec"] = _sig(
            geister["updates_per_sec"]
        )
        result["extra"]["geister_rnn_trained_env_steps_per_sec"] = _sig(
            geister["trained_env_steps_per_sec"], 5
        )

    _run_stage(result, "geister", stage_geister)

    # 4b. recurrent on-device self-play: Geister with the DRC ConvLSTM —
    # turn-based streaming lanes carrying per-player hidden state
    def stage_geister_device_selfplay():
        gsd = _streaming_selfplay_bench(
            "Geister", {"observation": True}, T_GEN / 2,
            n_lanes=128, k_steps=32,
        )
        result["extra"]["geister_device_selfplay_env_steps_per_sec"] = round(
            gsd["env_steps_per_sec"], 1
        )
        result["extra"]["geister_device_selfplay_episodes_per_sec"] = _sig(
            gsd["episodes_per_sec"]
        )
        if gsd["episodes_note"]:
            result["extra"]["geister_device_selfplay_episodes_note"] = gsd["episodes_note"]

    _run_stage(result, "geister-device-selfplay", stage_geister_device_selfplay)

    # 4b2. the standalone serving plane under client load (ROADMAP item 2):
    # saturation QPS + p50/p99 over the real socket transport, shed rate at
    # two offered loads against a tight SLO, hot-swap TTFR + zero-drop count
    def stage_serving():
        sv = _serving_bench(T_TRAIN)
        result["extra"]["serving_saturation_qps"] = _sig(sv["saturation_qps"])
        result["extra"]["serving_p50_ms"] = _sig(sv["p50_ms"])
        result["extra"]["serving_p99_ms"] = _sig(sv["p99_ms"])
        result["extra"]["serving_requests"] = sv["requests"]
        result["extra"]["serving_clients"] = sv["clients"]
        result["extra"]["serving_swap_warm_ms"] = _sig(sv["swap_warm_ms"])
        if sv["swap_ttfr_ms"] is not None:
            result["extra"]["serving_swap_ttfr_ms"] = _sig(sv["swap_ttfr_ms"])
        result["extra"]["serving_swap_dropped"] = sv["swap_dropped"]
        result["extra"]["serving_swap_flip_observed"] = sv["swap_flip_observed"]
        for tag in ("low", "high"):
            result["extra"][f"serving_offered_{tag}_qps"] = _sig(
                sv[f"offered_{tag}_qps"]
            )
            result["extra"][f"serving_shed_rate_{tag}"] = round(
                sv[f"shed_rate_{tag}"], 4
            )
        if sv["load_errors"] or sv["errors_low"] or sv["errors_high"]:
            result["error"] = (result["error"] or "") + (
                f" serving: {sv['load_errors']}+{sv['errors_low']}"
                f"+{sv['errors_high']} non-shed request failures"
            )
        if sv["swap_dropped"]:
            result["error"] = (result["error"] or "") + (
                f" serving: hot-swap dropped {sv['swap_dropped']} requests"
            )

    _run_stage(result, "serving", stage_serving)

    # 3f. fleet tier over the serving plane (docs/serving.md §Fleet):
    # router saturation with one vs two REAL replica processes (the tier
    # must scale ~linearly, not merely proxy), fleet-wide hot-swap under
    # session load with a zero-drop bar, and the server-resident session
    # leg's wire savings at bit-identical outputs
    def stage_fleet():
        fl = _fleet_bench(T_TRAIN)
        result["extra"]["fleet_qps_1"] = _sig(fl["qps_1"])
        result["extra"]["fleet_qps_2"] = _sig(fl["qps_2"])
        result["extra"]["fleet_scaling_x"] = round(fl["scaling_x"], 3)
        result["extra"]["fleet_cores"] = fl["cores"]
        result["extra"]["fleet_requests"] = fl["requests_1"] + fl["requests_2"]
        result["extra"]["fleet_clients"] = fl["clients"]
        result["extra"]["fleet_sessions"] = fl["clients"] * fl["sessions"]
        if fl["swap_warm_ms"] is not None:
            result["extra"]["fleet_swap_warm_ms"] = _sig(fl["swap_warm_ms"])
        result["extra"]["fleet_swap_replicas"] = fl["swap_replicas"]
        result["extra"]["fleet_swap_dropped"] = fl["swap_dropped"]
        result["extra"]["fleet_swap_flip_observed"] = fl["swap_flip_observed"]
        result["extra"]["fleet_session_wire_ratio"] = round(
            fl["session_wire_ratio"], 2
        )
        result["extra"]["fleet_session_bitident"] = fl["session_bitident"]
        result["extra"]["fleet_session_bytes_per_req"] = fl[
            "session_bytes_per_req"
        ]
        result["extra"]["fleet_ship_bytes_per_req"] = fl["ship_bytes_per_req"]
        # elastic leg (docs/serving.md §Elastic fleet): shed-free scale-up
        # under the storm, zero-loss scale-down migration, handoff wall ms
        result["extra"]["fleet_elastic_scale_ups"] = fl["elastic_scale_ups"]
        result["extra"]["fleet_elastic_scale_downs"] = fl[
            "elastic_scale_downs"
        ]
        result["extra"]["fleet_elastic_storm_requests"] = fl[
            "elastic_storm_requests"
        ]
        result["extra"]["fleet_elastic_storm_errors"] = fl[
            "elastic_storm_errors"
        ]
        result["extra"]["fleet_elastic_scaleup_shed"] = fl[
            "elastic_scaleup_shed"
        ]
        result["extra"]["fleet_elastic_sessions_migrated"] = fl[
            "elastic_sessions_migrated"
        ]
        result["extra"]["fleet_elastic_handoff_ms"] = fl["elastic_handoff_ms"]
        result["extra"]["fleet_elastic_migrated_session_ok"] = fl[
            "elastic_migrated_session_ok"
        ]
        if fl["elastic_storm_errors"] or fl["elastic_scaleup_shed"]:
            result["error"] = (result["error"] or "") + (
                f" fleet: elastic storm shed/errored "
                f"({fl['elastic_scaleup_shed']} shed, "
                f"{fl['elastic_storm_errors']} errors)"
            )
        if not fl["elastic_migrated_session_ok"]:
            result["error"] = (result["error"] or "") + (
                " fleet: migrated session lost on scale-down"
            )
        if fl["load_errors"]:
            result["error"] = (result["error"] or "") + (
                f" fleet: {fl['load_errors']} request failures under load"
            )
        if fl["swap_dropped"]:
            result["error"] = (result["error"] or "") + (
                f" fleet: hot-swap dropped {fl['swap_dropped']} requests"
            )
        if not fl["session_bitident"]:
            result["error"] = (result["error"] or "") + (
                " fleet: session outputs diverged from ship-state"
            )

    _run_stage(result, "fleet", stage_fleet)

    # 3g. league plane + the twin-less env compiler (ROADMAP item 4): the
    # autovec-vs-hand-twin per-chip frac at the >= 0.5 bar, lifted
    # ConnectFour with NO hand twin, and a small end-to-end league run's
    # payoff coverage / Elo spread / promotions
    def stage_league():
        lg = _league_bench(T_TRAIN)
        result["extra"]["league_twin_steps_per_sec"] = _sig(
            lg["twin_steps_per_sec"], 4
        )
        result["extra"]["league_autovec_steps_per_sec"] = _sig(
            lg["autovec_steps_per_sec"], 4
        )
        result["extra"]["league_autovec_per_chip_frac"] = _sig(
            lg["autovec_per_chip_frac"]
        )
        result["extra"]["league_autovec_target_met"] = lg["autovec_target_met"]
        result["extra"]["league_connectfour_autovec_steps_per_sec"] = _sig(
            lg["connectfour_autovec_steps_per_sec"], 4
        )
        result["extra"]["league_population"] = lg["population"]
        result["extra"]["league_promotions"] = lg["promotions"]
        result["extra"]["league_matches"] = lg["matches"]
        result["extra"]["league_payoff_coverage"] = round(
            lg["payoff_coverage"], 4
        )
        if lg["elo_spread"] is not None:
            result["extra"]["league_elo_spread"] = _sig(lg["elo_spread"], 4)
        result["extra"]["league_run_seconds"] = _sig(lg["run_seconds"], 4)
        if not lg["autovec_target_met"]:
            result["error"] = (result["error"] or "") + (
                " league: autovec per-chip frac %.3f below the 0.5 bar"
                % lg["autovec_per_chip_frac"]
            )

    _run_stage(result, "league", stage_league)

    # 3h. low-precision fast path (docs/performance.md §Low-precision):
    # both precision rungs measured in one session — weight/obs bytes
    # moved, engine rate and train updates/s per rung, the measured
    # calibration record, and the pinned wp-parity verdict
    def stage_lowprec():
        lp = _lowprec_bench(T_TRAIN)
        result["extra"]["lowprec_backend_note"] = (
            f"{lp['backend']}: byte ratios exact/portable; rates are "
            "proxy off-TPU (no MXU/HBM)" if lp["backend"] != "tpu"
            else "tpu"
        )
        result["extra"]["lowprec_weight_bytes_fp32"] = lp["weight_bytes_fp32"]
        result["extra"]["lowprec_weight_bytes_int8"] = lp["weight_bytes_int8"]
        result["extra"]["lowprec_weight_bytes_ratio"] = round(
            lp["weight_bytes_ratio"], 3
        )
        result["extra"]["lowprec_infer_qps_fp32"] = _sig(lp["infer_qps_fp32"])
        result["extra"]["lowprec_infer_qps_int8"] = _sig(lp["infer_qps_int8"])
        result["extra"]["lowprec_infer_int8_vs_fp32"] = round(
            lp["infer_int8_vs_fp32"], 3
        )
        result["extra"]["lowprec_calib_batches"] = lp["calib_batches"]
        result["extra"]["lowprec_calib_max_dev"] = lp["calib_max_dev"]
        result["extra"]["lowprec_calib_mean_dev"] = lp["calib_mean_dev"]
        result["extra"]["lowprec_obs_bytes_ratio"] = round(
            lp["obs_bytes_ratio"], 3
        )
        result["extra"]["lowprec_wire_bytes_ratio"] = round(
            lp["wire_bytes_ratio"], 3
        )
        result["extra"]["lowprec_train_updates_per_sec_fp32"] = _sig(
            lp["train_updates_per_sec_fp32"]
        )
        result["extra"]["lowprec_train_updates_per_sec_int8"] = _sig(
            lp["train_updates_per_sec_int8"]
        )
        result["extra"]["lowprec_train_int8_vs_fp32"] = round(
            lp["train_int8_vs_fp32"], 3
        )
        result["extra"]["lowprec_wp"] = round(lp["wp"], 4)
        result["extra"]["lowprec_wp_games"] = lp["wp_games"]
        result["extra"]["lowprec_wp_delta"] = round(lp["wp_delta"], 4)
        result["extra"]["lowprec_wp_parity_target_met"] = lp[
            "wp_parity_target_met"
        ]
        if not lp["wp_parity_target_met"] and not QUICK:
            result["error"] = (result["error"] or "") + (
                " lowprec: |wp - 0.5| = %.4f above the 0.03 parity bar "
                "over %d games" % (lp["wp_delta"], lp["wp_games"])
            )

    _run_stage(result, "lowprec", stage_lowprec)

    # 3i. data flywheel (docs/serving.md §Data flywheel): harvest assembly
    # rate over the real wire, ingest drain bytes/s, and the quality
    # plane's promotion-gate and sentinel-demote latencies
    def stage_flywheel():
        fw = _flywheel_bench(T_TRAIN)
        result["extra"]["flywheel_episodes"] = fw["episodes"]
        result["extra"]["flywheel_harvest_eps_per_sec"] = _sig(
            fw["harvest_eps_per_sec"]
        )
        result["extra"]["flywheel_pull_episodes"] = fw["pull_episodes"]
        result["extra"]["flywheel_ingest_bytes_per_sec"] = _sig(
            fw["ingest_bytes_per_sec"]
        )
        result["extra"]["flywheel_dropped"] = fw["dropped"]
        result["extra"]["flywheel_promote_latency_ms"] = _sig(
            fw["promote_latency_ms"]
        )
        result["extra"]["flywheel_demote_ms"] = _sig(fw["demote_ms"])
        result["extra"]["flywheel_promotions"] = fw["promotions"]
        result["extra"]["flywheel_demotions"] = fw["demotions"]
        result["extra"]["flywheel_live_games"] = fw["games"]
        if fw["dropped"]:
            result["error"] = (result["error"] or "") + (
                f" flywheel: {fw['dropped']} harvested episodes dropped"
            )
        if not fw["promote_observed"]:
            result["error"] = (result["error"] or "") + (
                " flywheel: gated promotion never flipped"
            )
        if not fw["demote_observed"]:
            result["error"] = (result["error"] or "") + (
                " flywheel: quality sentinel never demoted"
            )

    _run_stage(result, "flywheel", stage_flywheel)

    # 4c. turn-mode device-resident replay: Geister DRC trained straight
    # from device rings (all-player burn-in windows, runtime/device_replay
    # turn mode) concurrent with streaming self-play — TPU-gated: on CPU
    # the DRC window compile dominates any timed window
    def stage_geister_devreplay():
        gdr = _geister_device_replay_bench(T_TRAIN)
        if "skipped" in gdr:  # benign prefill timeout, like stage 3d
            result["extra"]["geister_devreplay_note"] = gdr["skipped"]
            return
        result["extra"]["geister_devreplay_updates_per_sec"] = _sig(
            gdr["updates_per_sec"]
        )
        result["extra"]["geister_devreplay_trained_env_steps_per_sec"] = _sig(
            gdr["trained_env_steps_per_sec"], 5
        )
        result["extra"]["geister_devreplay_selfplay_env_steps_per_sec"] = _sig(
            gdr["selfplay_env_steps_per_sec"]
        )
        if not gdr["loss_finite"]:
            result["error"] = (result["error"] or "") + " geister-devreplay: non-finite loss"

    # 4d. MXU-saturation probe: the generic transformer family
    # (models/transformer.py) scaled to matmul-dominated shapes via
    # env_args.net_args, through the SAME TrainContext path as every other
    # stage — real env (Geister windows, ~full-length episodes), real
    # losses, Adam, whole-window einsum attention (the measured winner at
    # the pinned T64 shape; flash wins at T >= flash_min_t), bf16 compute
    # with fp32 master weights.  The game-net MFUs (tictactoe/geese/northstar2) are
    # honest-but-tiny because those convs are tiny; this stage states the
    # framework's MFU where the model actually offers the MXU work.
    def stage_transformer():
        import jax

        on_tpu = jax.default_backend() == "tpu"
        if on_tpu:
            # shapes from the 2026-08-01/02 v5e sweeps (tools/tune_transformer.py):
            # T64 windows amortize the step's fixed ops best (d768: MFU 0.311
            # vs 0.253 at T32), doubling batch was flat (0.247 — already
            # device-bound at B64), widening to d1024 lifts the matmul share
            # (0.347 under flash), and einsum attention at this short window
            # lifts it again: 18.6 updates/s, MFU 0.48 (2026-08-02)
            net_args = TRANSFORMER_TPU_NET_ARGS
            t_over = dict(TRANSFORMER_TPU_OVERRIDES)
        else:
            # tiny-shape coverage of the identical code path (einsum
            # attention: the Pallas kernel is TPU-only)
            net_args = {"d_model": 96, "n_heads": 4, "n_layers": 2,
                        "memory_len": 16}
            t_over = {"batch_size": 8, "burn_in_steps": 2,
                      "forward_steps": 14, "observation": True,
                      "seq_attention": "einsum"}
        # no fused variant: the k-step lax.scan of this big step compiled
        # to a SLOWER per-update program than the pipelined single-dispatch
        # loop (19.8 vs 35.1 updates/s, v5e 2026-08-01) and costs a second
        # multi-minute compile — dispatch amortization only pays when the
        # step is dispatch-bound, i.e. the tiny game nets
        tr = _train_bench(
            "Geister", t_over, T_TRAIN, n_dev,
            fill_episodes=8,
            env_overrides={"net": "transformer", "net_args": net_args},
        )
        result["extra"]["transformer_net"] = (
            f"d{net_args['d_model']} L{net_args['n_layers']} "
            f"H{net_args['n_heads']} T{t_over['burn_in_steps'] + t_over['forward_steps']} "
            f"B{t_over['batch_size']}x2p "
            + ("bf16" if t_over.get("compute_dtype") else "fp32")
        )
        result["extra"]["transformer_updates_per_sec"] = _sig(tr["updates_per_sec"])
        ups = tr["updates_per_sec"]
        tokens = (t_over["batch_size"] * 2
                  * (t_over["burn_in_steps"] + t_over["forward_steps"]))
        result["extra"]["transformer_tokens_per_sec"] = _sig(ups * tokens, 4)
        if tr["flops_per_step"]:
            result["extra"]["transformer_flops_per_step"] = tr["flops_per_step"]
            if peak:
                result["extra"]["transformer_mfu"] = _sig(
                    tr["flops_per_step"] * ups / (peak * n_dev)
                )
            else:
                result["extra"]["transformer_mfu"] = None
                result["extra"]["transformer_mfu_note"] = (
                    "no peak-FLOPs table entry for device kind "
                    f"'{getattr(devices[0], 'device_kind', '?')}'"
                )
        else:
            result["extra"]["transformer_mfu"] = None
            result["extra"]["transformer_mfu_note"] = "no flops from any lowering"
    _run_stage(result, "transformer", stage_transformer)

    # 4e. long-context transformer at production shapes (ROADMAP item 5):
    # T {64, 512, 1024} x attention mode {einsum, flash, auto} + an sp=2
    # ring leg, all through the one TrainContext training semantics — the
    # stage that records the d1536 flash-vs-einsum crossover, the remat
    # ladder's HBM headroom, and the transformer_long_mfu >= 0.40 verdict.
    # Runs on every backend: the CPU leg (tiny pins, interpret-mode
    # Pallas) is the CI smoke that keeps the sweep + auto-pick + ring
    # composition from rotting unexercised between TPU captures.
    def stage_transformer_long():
        tl = _transformer_long_bench(T_TRAIN, n_dev, peak)
        for name, p in tl["points"].items():
            key = f"transformer_long_{name}"
            result["extra"][f"{key}_updates_per_sec"] = _sig(p["updates_per_sec"])
            result["extra"][f"{key}_tokens_per_sec"] = _sig(p["tokens_per_sec"], 4)
            result["extra"][f"{key}_attn"] = p["attn"]
            result["extra"][f"{key}_remat"] = p["remat"]
            if p["mfu"] is not None:
                result["extra"][f"{key}_mfu"] = _sig(p["mfu"])
            if p["peak_bytes"]:
                result["extra"][f"{key}_peak_hbm_bytes"] = p["peak_bytes"]
        if tl["sp2"]:
            sp = tl["sp2"]
            result["extra"]["transformer_long_sp2_updates_per_sec"] = _sig(
                sp["updates_per_sec"]
            )
            result["extra"]["transformer_long_sp2_tokens_per_sec"] = _sig(
                sp["tokens_per_sec"], 4
            )
            result["extra"]["transformer_long_sp2_attn"] = sp["attn"]
            if sp["mfu"] is not None:
                result["extra"]["transformer_long_sp2_mfu"] = _sig(sp["mfu"])
        if tl["sp2_note"]:
            result["extra"]["transformer_long_sp2_note"] = tl["sp2_note"]
        if tl["remat_headroom"]:
            result["extra"]["transformer_long_remat_headroom"] = tl["remat_headroom"]
        if tl["mfu"] is not None:
            result["extra"]["transformer_long_mfu"] = _sig(tl["mfu"])
        result["extra"]["transformer_long_target_met"] = tl["target_met"]

    _run_stage(result, "transformer_long", stage_transformer_long)

    # 5. seq-attention kernel crossover (einsum vs Pallas flash, fwd+bwd)
    def stage_flash():
        result["extra"]["flash_attention"] = _flash_attention_bench()

    import jax

    if jax.default_backend() == "tpu":
        _run_stage(result, "geister-devreplay", stage_geister_devreplay)
        _run_stage(result, "flash", stage_flash)  # kernel path is TPU-only

    _emit_snapshot(result, final=True)


if __name__ == "__main__":
    main()
