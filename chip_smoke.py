"""The quickest proof that the system still starts, compiles and steps on
the chip: one process, one TPU chip, the entry points a user calls.

    python chip_smoke.py              # one chip: train-host, train-device,
                                      #   transformer, serve
    python chip_smoke.py --multichip  # four chips: the cross-chip checks only

A smoke, not a benchmark: model widths, batch and window are real, run
length is short (epochs, games, requests), weights are random from
``--seed``.  One line per phase says what ran, which code path it took,
wall time and compile time; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and nothing else prints ``"ok": true``.  A failed phase, a fallback that
fired (a degraded pipeline, a skipped device eval, a cold-start serve), or
a device that is not a TPU exits non-zero.  Writes only under
``chip_smoke_out/`` (and the compile cache, see
handyrl_tpu/utils/compile_cache.py).
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import multiprocessing
import os
import shutil
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")

# The transformer phase's model and step: the benchmark's ``xfmr_d1536``
# configuration under its ``xfmr_train_t64`` workload
# (tests/test_chip_smoke.py holds the two equal), and the long-window rows
# the Pallas kernels are compiled at (tests/test_chip_compile.py).
TRANSFORMER_TPU_NET_ARGS = {"d_model": 1536, "n_heads": 16, "n_layers": 8,
                            "memory_len": 32}
TRANSFORMER_TPU_OVERRIDES = {"batch_size": 64, "burn_in_steps": 2,
                             "forward_steps": 62, "observation": True,
                             "compute_dtype": "bfloat16",
                             # 'auto' would pick einsum at T64 too; pinned
                             # so the phase measures one known program
                             "seq_attention": "einsum"}
# batch shrinks with T so that remat, not a vanishing batch, is what fits
# T1024 in HBM
TRANSFORMER_LONG_TPU = {
    "net_args": TRANSFORMER_TPU_NET_ARGS,
    "sweep_t": (64, 512, 1024),
    "batch_by_t": {64: 64, 512: 16, 1024: 8},
    "flash_min_t": 128,
    "compute_dtype": "bfloat16",
    "sp_t": 512,
    "sp_batch": 16,
}

# real where the model is (widths, batch, window), short where only
# length is at stake (epochs, episodes, requests)
SIZES = {
    "train_host": {
        # the shipped config.yaml, only these three shortened
        "epochs": 3, "update_episodes": 100, "minimum_episodes": 200,
    },
    "train_device": {
        # the README's north-star loop (HungryGeese, rollout -> rings -> train)
        "batch_size": 128, "forward_steps": 16,
        "device_rollout_games": 128, "device_replay_k_steps": 32,
        "device_replay_slots": 512, "fused_steps": 8,
        "device_eval_games": 64,
        # one rollout dispatch (128 lanes x 32 steps of early random play)
        # finishes ~800 episodes, so an epoch is two dispatches
        "epochs": 3, "update_episodes": 1600, "minimum_episodes": 1600,
    },
    "transformer": {"net_args": TRANSFORMER_TPU_NET_ARGS,
                    "overrides": TRANSFORMER_TPU_OVERRIDES, "steps": 3},
    "serve": {"games": 3, "max_steps": 12},
    # --multichip
    "dp": {"batch_size": 128, "device_rollout_games": 128,
           "device_replay_k_steps": 32, "device_replay_slots": 512,
           "fused_steps": 8, "dispatches": 3},
    # the longest row's per-head shape: (2, 1024, 16, 96), window 32
    "ring": {
        "shape": (2, TRANSFORMER_LONG_TPU["sweep_t"][-1],
                  TRANSFORMER_TPU_NET_ARGS["n_heads"],
                  TRANSFORMER_TPU_NET_ARGS["d_model"] // TRANSFORMER_TPU_NET_ARGS["n_heads"]),
        "window": TRANSFORMER_TPU_NET_ARGS["memory_len"],
    },
}

# the bound tests/test_flash_attention.py holds the bf16 kernel to against
# the exact reference (rtol and atol alike)
BF16_TOL = 3e-2

# a line of these in a phase's output means a fallback hid the real path
FALLBACK_MARKERS = (
    "falling back", "degrading", "device generation stops",
    "giving up on the rollout thread", "starting fresh", "Traceback",
)

ON_CHIP = False  # set by main(); a CPU rehearsal skips the chip-only asserts


class _Tee:
    def __init__(self, stream, sink):
        self.stream, self.sink = stream, sink

    def write(self, text):
        self.sink.write(text)
        return self.stream.write(text)

    def flush(self):
        self.sink.flush()
        self.stream.flush()

    def __getattr__(self, name):
        return getattr(self.stream, name)


@contextlib.contextmanager
def _phase_dir(name):
    """Run a phase inside ``chip_smoke_out/<name>/`` (the learner writes
    metrics.jsonl and models/ relative to the cwd), teeing its output to
    ``log.txt`` there; yields (dir, read_log)."""
    path = os.path.join(OUT, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    log_path = os.path.join(path, "log.txt")
    prev = os.getcwd()
    os.chdir(path)
    with open(log_path, "w") as sink:

        def read_log():
            sink.flush()
            with open(log_path) as f:
                return f.read()

        out, err = sys.stdout, sys.stderr
        sys.stdout, sys.stderr = _Tee(out, sink), _Tee(err, sink)
        try:
            yield path, read_log
        finally:
            sys.stdout, sys.stderr = out, err
            os.chdir(prev)


def _assert_no_fallback(log_text):
    hits = [m for m in FALLBACK_MARKERS if m in log_text]
    assert not hits, f"fallback/failure marker(s) in the phase output: {hits}"


def _train(cfg):
    """What ``python main.py --train`` does after loading its config."""
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.parallel import init_distributed
    from handyrl_tpu.runtime.learner import train_main

    args = normalize_args(cfg)
    init_distributed(args["train_args"].get("distributed"))
    before = set(threading.enumerate())
    train_main(args)
    # threads and children the run started must be gone (daemon threads
    # parked in a blocking get are given a moment to notice the stop)
    deadline = time.monotonic() + 15.0
    while time.monotonic() < deadline:
        left = [t for t in threading.enumerate() if t not in before and t.is_alive()]
        kids = multiprocessing.active_children()
        if not left and not kids:
            break
        time.sleep(0.2)
    assert not kids, f"child processes outlived the run: {kids}"
    assert not left, f"threads outlived the run: {[t.name for t in left]}"
    with open("metrics.jsonl") as f:
        return [json.loads(line) for line in f if line.strip()]


def _check_training(records):
    import math

    assert len(records) >= 2, f"{len(records)} epoch records"
    last = records[-1]
    assert last["steps"] > 0, "no SGD update ran"
    losses = [r["loss"] for r in records if "loss" in r]
    assert losses and all(
        math.isfinite(v) for loss in losses for v in loss.values()
    ), f"non-finite loss: {losses}"
    if ON_CHIP:
        # the device kind was in the peak table and the FLOPs trace worked
        assert any("mfu" in r for r in records), "no mfu stat in metrics.jsonl"
    assert os.path.exists("models/latest.ckpt"), "models/latest.ckpt missing"
    return last, losses[-1]


def phase_train_host(sizes):
    import yaml

    with open(os.path.join(ROOT, "config.yaml")) as f:
        cfg = yaml.safe_load(f)
    cfg["train_args"].update(sizes)
    with _phase_dir("train-host") as (_, read_log):
        records = _train(cfg)
        last, loss = _check_training(records)
        assert last["pipeline"] == "shm", f"pipeline ran as {last['pipeline']!r}"
        for key in ("pipe_batcher_fallback", "pipe_batcher_deaths"):
            assert all(r.get(key, 0) == 0 for r in records), f"{key} != 0"
        _assert_no_fallback(read_log())
    return {
        "env": cfg["env_args"]["env"], "pipeline": last["pipeline"],
        "epochs": len(records), "updates": last["steps"],
        "episodes": last["episodes"], "loss_total": round(loss["total"], 4),
        "sentinel_spike_steps": last.get("sentinel_spike_steps"),
        "mfu": last.get("mfu"),
    }


def phase_train_device(sizes):
    cfg = {
        "env_args": {"env": "HungryGeese"},
        "train_args": dict(
            sizes,
            turn_based_training=False, observation=False,
            device_replay=True, eval_rate=0.0,
            eval={"opponent": ["rulebase"]},
            worker={"num_parallel": 1},
        ),
    }
    with _phase_dir("train-device") as (path, read_log):
        records = _train(cfg)
        last, loss = _check_training(records)
        # episodes came from the device rollout, never through the host
        assert any(r.get("device_mean_episode_len", 0) > 1 for r in records), (
            "no device-rollout episodes were booked"
        )
        # the device-eval record is there, every epoch (a failed device
        # eval raises in the learner; the host worker alone may idle)
        assert all(r.get("win_rate") for r in records), "an epoch has no win rate"
        for key in ("plane_watchdog_stalls", "plane_watchdog_degraded"):
            assert all(r.get(key, 0) == 0 for r in records), f"{key} != 0"
        _assert_no_fallback(read_log())
        model_dir = os.path.join(path, "models")
    return {
        "env": "HungryGeese", "plane": last.get("plane"),
        "epochs": len(records), "updates": last["steps"],
        "episodes": last["episodes"], "loss_total": round(loss["total"], 4),
        "win_rate": last["win_rate"], "mfu": last.get("mfu"),
        "model_dir": model_dir, "epoch_written": last["epoch"] + 1,
    }


def phase_transformer(sizes, seed):
    """The widest model the repo supports, a few train steps through
    TrainContext, einsum and flash attention on the same params and batch."""
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import _random_play_batch
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import RandomModel, init_variables
    from handyrl_tpu.parallel import TrainContext, make_mesh

    net_args, overrides = sizes["net_args"], sizes["overrides"]
    random.seed(seed)
    np.random.seed(seed)
    info = {}
    with _phase_dir("transformer") as (_, read_log):
        base = None
        per_mode = {}
        for mode in ("einsum", "flash"):
            cfg = normalize_args({
                "env_args": {"env": "Geister", "net": "transformer",
                             "net_args": net_args},
                "train_args": dict(overrides, seq_attention=mode, seed=seed),
            })
            args = dict(cfg["train_args"], env=cfg["env_args"])
            env = make_env(args["env"])
            module = env.net()
            if base is None:
                params = init_variables(module, env, seed=seed)["params"]
                # windows of random play (the weights are random too:
                # shapes are what matter); the output spec is written out
                # so nothing runs the big net just to learn its shapes
                A = env.action_size()
                random_model = RandomModel({
                    "policy": ((A,), np.float32), "value": ((1,), np.float32),
                    "return": ((1,), np.float32),
                })
                batch = _random_play_batch(env, args, random_model, args["batch_size"])
                base = (params, batch)
                info["params_m"] = round(
                    sum(x.size for x in jax.tree.leaves(params)) / 1e6, 1
                )
                B, T, P = batch["action"].shape[:3]
                info["shape"] = "d%d L%d H%d B%dx%dp T%d %s" % (
                    module.d_model, module.n_layers, net_args["n_heads"],
                    B, P, T, args.get("compute_dtype") or "float32",
                )
            params, batch = base
            ctx = TrainContext(module, args, make_mesh({"dp": 1}))
            state = ctx.init_state(params)
            device_batch = ctx.put_batch(batch)
            losses = []
            t0 = time.perf_counter()
            for _ in range(sizes["steps"]):
                state, metrics = ctx.train_step(state, device_batch, 1e-5)
                m = jax.device_get(metrics)
                losses.append(float(m["total"]) / max(float(m["dcnt"]), 1.0))
            wall = time.perf_counter() - t0
            # the program the step ran, from the same bound jit (a
            # persistent-cache hit, not a second compile)
            avals = lambda tree: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
                tree,
            )
            compiled = ctx._bind(state).lower(
                avals(state), avals(device_batch),
                jax.ShapeDtypeStruct((), jnp.float32),
            ).compile()
            ma = compiled.memory_analysis()
            per_mode[mode] = {
                "losses": [round(x, 5) for x in losses],
                "kernel": "tpu_custom_call" in compiled.as_text(),
                "wall_s": round(wall, 1),
                "program_bytes": {
                    k: int(getattr(ma, k + "_size_in_bytes"))
                    for k in ("temp", "argument", "output", "alias")
                },
            }
            print(f"transformer {mode}: memory_analysis {ma}")
            assert all(np.isfinite(losses)), f"{mode}: non-finite loss {losses}"
            del state, device_batch, ctx, compiled
        e, f = per_mode["einsum"]["losses"], per_mode["flash"]["losses"]
        np.testing.assert_allclose(
            f, e, rtol=BF16_TOL,
            err_msg="flash and einsum losses differ beyond the bf16 bound",
        )
        assert not per_mode["einsum"]["kernel"], "einsum program holds a Pallas kernel"
        if ON_CHIP:
            # interpret resolved to False on this backend: the compiled
            # kernel, not its interpreter, is in the flash program
            assert per_mode["flash"]["kernel"], "no tpu_custom_call in the flash program"
        _assert_no_fallback(read_log())
    stats = jax.local_devices()[0].memory_stats() or {}
    info.update(per_mode, max_rel_diff=float(np.max(np.abs(np.subtract(f, e) / e))),
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    return info


def phase_serve(sizes, model_dir, epoch_written, seed):
    """The plane ``main.py --serve`` builds, on the checkpoint train-device
    wrote, answering moves a ServingClient sends over TCP."""
    import numpy as np

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables
    from handyrl_tpu.runtime.checkpoint import load_verified_params
    from handyrl_tpu.serving import ServingClient, build_serving

    args = normalize_args({
        "env_args": {"env": "HungryGeese"},
        "train_args": {"model_dir": model_dir, "serving": {"port": 0},
                       "metrics_path": "metrics.jsonl"},
    })
    rng = np.random.default_rng(seed)
    with _phase_dir("serve") as (_, read_log):
        server = build_serving(args)
        client = ServingClient("127.0.0.1", server.bound_port)
        try:
            env = make_env(args["env_args"])
            module = env.net()
            template = init_variables(module, env)["params"]
            params = load_verified_params(model_dir, epoch_written, template)
            reference = InferenceModel(module, {"params": params})
            moves, max_dev = 0, 0.0
            for _ in range(sizes["games"]):
                env.reset()
                for _ in range(sizes["max_steps"]):
                    if env.terminal():
                        break
                    players = env.turns()
                    obs = {p: env.observation(p) for p in players}
                    futures = {p: client.submit(obs[p]) for p in players}
                    actions = {}
                    for p in players:
                        reply = futures[p].result(timeout=120.0)
                        assert reply["model"] == epoch_written, (
                            f"served snapshot {reply['model']}, wrote {epoch_written}"
                        )
                        policy = np.asarray(reply["out"]["policy"])
                        assert policy.shape == (env.action_size(),), policy.shape
                        assert np.isfinite(policy).all(), "non-finite policy"
                        want = np.asarray(reference.inference(obs[p])["policy"])
                        max_dev = max(max_dev, float(np.abs(policy - want).max()))
                        legal = env.legal_actions(p)
                        logits = np.full(policy.shape, -np.inf)
                        logits[legal] = policy[legal]
                        prob = np.exp(logits - logits.max())
                        actions[p] = int(rng.choice(len(prob), p=prob / prob.sum()))
                        assert actions[p] in legal, "the served move is illegal"
                        moves += 1
                    env.step(actions)
            # the served policy is the checkpoint's: the TPU runs fp32
            # convs and matmuls as bf16 passes, and the server's padded
            # batch bucket is another program than the direct batch of 1
            assert max_dev < BF16_TOL, f"served vs direct policy differ by {max_dev}"
            stats = server.stats_record()
        finally:
            client.close()
            server.shutdown()
        assert stats["serve_replies"] == moves, (stats["serve_replies"], moves)
        for key in ("serve_shed", "serve_deadline_miss", "serve_errors",
                    "serve_snapshot_substituted"):
            assert stats[key] == 0, f"{key} = {stats[key]}"
        _assert_no_fallback(read_log())
    return {
        "served_model": epoch_written, "moves": moves,
        "batches": stats["serve_batches"], "p50_ms": stats["serve_p50_ms"],
        "p99_ms": stats["serve_p99_ms"], "max_dev_vs_direct": max_dev,
    }


# ---------------------------------------------------------------------------
# --multichip: the paths that exist only across chips, each against the
# same work on one device.  Building blocks shared with the virtual-device
# dry run (__graft_entry__._dryrun_multichip_impl).
# ---------------------------------------------------------------------------


def _geese_setup(batch_size):
    from __graft_entry__ import _tiny_batch
    from handyrl_tpu.config import normalize_args

    cfg = normalize_args({
        "env_args": {"env": "HungryGeese"},
        "train_args": {"turn_based_training": False, "observation": False,
                       "batch_size": batch_size, "forward_steps": 16},
    })
    args = dict(cfg["train_args"], env=cfg["env_args"])
    module, batch, params = _tiny_batch(args, batch_size)
    return args, module, batch, params


def _sections_setup(batch_size):
    """A transformer small enough to compile in seconds whose mlp kernels
    (2^18 elements at d256) ride the gradient ring: on a dp mesh its
    sections sum their own gradient (mesh.sum_section_grads)."""
    from __graft_entry__ import _tiny_batch
    from handyrl_tpu.config import normalize_args

    cfg = normalize_args({
        "env_args": {"env": "TicTacToe", "net": "transformer",
                     "net_args": {"d_model": 256, "n_heads": 4, "n_layers": 2}},
        "train_args": {"observation": True, "burn_in_steps": 2, "forward_steps": 4,
                       "compress_steps": 4, "batch_size": batch_size,
                       "compute_dtype": "bfloat16"},
    })
    args = dict(cfg["train_args"], env=cfg["env_args"])
    module, batch, params = _tiny_batch(args, batch_size)
    return args, module, batch, params


def _assert_spans(tree, n, what):
    import jax

    sizes = {len(x.sharding.device_set) for x in jax.tree.leaves(tree)}
    assert sizes == {n}, f"{what}: arrays span {sizes} devices, not {n}"


def _assert_same_on_every_chip(tree, what):
    """Replicated arrays hold the same bits on every chip."""
    import jax
    import numpy as np

    for x in jax.tree.leaves(tree):
        first, *rest = (np.asarray(s.data) for s in x.addressable_shards)
        assert all((r == first).all() for r in rest), f"{what}: replicas differ"


def phase_dp_train_step(sizes, setup=_geese_setup):
    """One train step on a dp=4 mesh against the same batch and params on
    a one-device mesh: GeeseNet (GSPMD sums its gradient), or with
    ``_sections_setup`` a transformer whose sections ring their own."""
    import jax
    import numpy as np

    from handyrl_tpu.parallel import TrainContext, make_mesh

    args, module, batch, params = setup(sizes["batch_size"])
    lr, out = 1e-4, {}
    for dp in (4, 1):
        ctx = TrainContext(module, args, make_mesh({"dp": dp}))
        state = ctx.init_state(params)
        device_batch = ctx.put_batch(batch)
        if dp > 1:
            _assert_spans((state, device_batch), dp, "dp train step inputs")
            rows = {x.addressable_shards[0].data.shape[0]
                    for x in jax.tree.leaves(device_batch)}
            assert rows == {sizes["batch_size"] // dp}, f"batch shard rows {rows}"
        state, metrics = ctx.train_step(state, device_batch, lr)
        if dp > 1:
            _assert_spans(state, dp, "dp train step outputs")
            _assert_same_on_every_chip(state["params"], "dp train step params")
            grad_sync = ctx.grad_sync
        m = jax.device_get(metrics)
        out[dp] = (float(m["total"]) / float(m["dcnt"]),
                   jax.device_get(state["params"]))
    (loss4, p4), (loss1, p1) = out[4], out[1]
    np.testing.assert_allclose(loss4, loss1, rtol=1e-3)
    # Adam's first update is lr * g / (|g| + eps): a parameter whose
    # gradient is noise around zero may land on either side, 2 lr apart.
    # Everything else must agree far inside one update
    diff = np.concatenate([
        np.abs(a - b).ravel() for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p1))
    ])
    assert diff.max() <= 2.1 * lr, f"params differ by {diff.max()}"
    flipped = float((diff > 0.1 * lr).mean())
    assert flipped < 1e-2, f"{flipped:.2%} of the params disagree past 0.1 lr"
    return {"loss_dp4": round(loss4, 6), "loss_dp1": round(loss1, 6),
            "params_max_diff": float(diff.max()), "params_flipped_frac": flipped,
            "grad_sync": grad_sync}


def phase_dp_rollout_replay(sizes):
    """Streaming self-play with lanes sharded over dp=4, ingested into
    dp-sharded device rings, and the fused sample+train step on them —
    against the same loop on one device."""
    import jax
    import numpy as np

    from handyrl_tpu.envs.vector_hungry_geese import VectorHungryGeese as venv
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.parallel.mesh import dispatch_serialized
    from handyrl_tpu.runtime.device_replay import DeviceReplay
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn

    args, module, _, params = _geese_setup(sizes["batch_size"])
    lanes = sizes["device_rollout_games"]
    out = {}
    for dp in (4, 1):
        mesh = make_mesh({"dp": dp})
        fn = build_streaming_fn(venv, module, lanes, sizes["device_replay_k_steps"],
                                mesh=mesh if dp > 1 else None, use_observe_mask=False)
        replay = DeviceReplay(venv, module, args, mesh, lanes,
                              slots=sizes["device_replay_slots"])
        vstate = venv.init(lanes, jax.random.PRNGKey(3))
        key = jax.random.PRNGKey(4)
        for _ in range(sizes["dispatches"]):
            key, sub = jax.random.split(key)
            vstate, _, records = dispatch_serialized(
                lambda: fn(params, vstate, None, sub), mesh
            )
            if dp > 1:
                _assert_spans(records, dp, "rollout records")
            replay.ingest_counted(records)
        if dp > 1:
            _assert_spans(replay.rings, dp, "device rings")
        eligible = replay.eligible_count()
        assert eligible >= sizes["batch_size"], f"{eligible} sampleable windows"
        ctx = TrainContext(module, args, mesh)
        train = replay.train_fn(ctx, fused_steps=sizes["fused_steps"])
        state, metrics = train(ctx.init_state(params), jax.random.PRNGKey(5), 1e-4)
        m = jax.device_get(metrics)
        assert np.isfinite(m["total"]) and m["dcnt"] > 0, m
        if dp > 1:
            _assert_same_on_every_chip(state["params"], "dp replay train params")
        out[dp] = {"episodes": int(replay.counters["episodes"]),
                   "eligible": int(eligible),
                   "ent": float(m["ent"]) / float(m["dcnt"]),
                   "v": float(m["v"]) / float(m["dcnt"])}
    # the same seeds drive both; sharding changes float summation order,
    # which can flip a rare near-tie sample and so move a few episodes
    np.testing.assert_allclose(out[4]["episodes"], out[1]["episodes"], rtol=5e-2)
    np.testing.assert_allclose(out[4]["ent"], out[1]["ent"], rtol=2e-2)
    return {"dp4": out[4], "dp1": out[1]}


def phase_ring_attention(sizes, seed):
    """Ring attention with the window sharded over sp=4 (K/V rotating by
    ppermute) against exact attention on one device: the plain causal
    form and the production masked/ALiBi/window form."""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.ops import (
        full_attention_reference,
        masked_ring_self_attention,
        ring_self_attention,
    )
    from handyrl_tpu.ops.flash_attention import masked_attention_reference
    from handyrl_tpu.parallel import make_mesh

    B, T, H, D = sizes["shape"]
    mesh = make_mesh({"sp": 4})
    kq, kk, kv, km = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(x, (B, T, H, D), jnp.float32) for x in (kq, kk, kv))
    err = float(jnp.abs(
        ring_self_attention(q, k, v, mesh, causal=True)
        - full_attention_reference(q, k, v, causal=True)
    ).max())
    key_mask = (jax.random.uniform(km, (B, T)) < 0.7).astype(jnp.float32)
    slopes = 2.0 ** (-jnp.arange(1, H + 1, dtype=jnp.float32))
    masked_err = float(jnp.abs(
        masked_ring_self_attention(q, k, v, key_mask, slopes, mesh,
                                   window=sizes["window"])
        - masked_attention_reference(q, k, v, key_mask, slopes,
                                     window=sizes["window"])
    ).max())
    assert err < BF16_TOL and masked_err < BF16_TOL, (err, masked_err)
    return {"shape": "B%d T%d H%d D%d" % (B, T, H, D), "sp": 4,
            "max_err": err, "masked_max_err": masked_err}


def _codec_accelerator():
    """Build (or load) the C wire-codec accelerator from the committed
    source; the run must not lean on a stale or missing .so."""
    from handyrl_tpu.runtime import _codec_build, codec

    mod = _codec_build.load()  # raises the compiler's error if the build fails
    assert codec.get_accel() is not None, "the pure-Python codec is active"
    return os.path.basename(mod.__file__)


def run_phases(phases):
    """Run ``phases`` ({name: thunk(results) -> info}) in order, one line
    each; returns the names that failed.  A phase whose thunk raises
    KeyError on a failed dependency's result counts as failed too."""
    from handyrl_tpu.utils.compile_cache import CompileCounters

    counters = CompileCounters()
    results, failed = {}, []
    for name, thunk in phases.items():
        c0, t0 = counters.snapshot(), time.perf_counter()
        try:
            info = thunk(results)
            results[name] = info
            status = "ok"
        except BaseException:
            traceback.print_exc()
            info, status = {}, "FAILED"
            failed.append(name)
        c1, wall = counters.snapshot(), time.perf_counter() - t0
        print("phase %s: %s %s" % (name, status, json.dumps({
            "wall_s": round(wall, 1),
            "compile_s": round(c1["compile_s"] - c0["compile_s"], 1),
            "cache_hits": c1["hits"] - c0["hits"],
            "cache_misses": c1["misses"] - c0["misses"],
            **info,
        }, default=str)), flush=True)
    return failed


def main(argv=None) -> int:
    global ON_CHIP
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="four chips: run the cross-chip checks only")
    parser.add_argument("--seed", type=int, default=0)
    opts = parser.parse_args(argv)

    import jax

    from handyrl_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    want = 4 if opts.multichip else 1
    if devices[0].platform != "tpu" or len(devices) != want:
        print(
            f"chip_smoke: needs {want} TPU chip(s); jax found "
            f"{len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        return 2
    ON_CHIP = True
    cache_dir = enable_compile_cache()
    os.makedirs(OUT, exist_ok=True)
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {cache_dir}; codec {_codec_accelerator()}", flush=True)

    if opts.multichip:
        phases = {
            "dp-train-step": lambda r: phase_dp_train_step(SIZES["dp"]),
            "dp-section-ring": lambda r: phase_dp_train_step(SIZES["dp"], _sections_setup),
            "dp-rollout-replay": lambda r: phase_dp_rollout_replay(SIZES["dp"]),
            "ring-attention": lambda r: phase_ring_attention(SIZES["ring"], opts.seed),
        }
    else:
        phases = {
            "train-host": lambda r: phase_train_host(SIZES["train_host"]),
            "train-device": lambda r: phase_train_device(SIZES["train_device"]),
            "transformer": lambda r: phase_transformer(SIZES["transformer"], opts.seed),
            "serve": lambda r: phase_serve(
                SIZES["serve"], r["train-device"]["model_dir"],
                r["train-device"]["epoch_written"], opts.seed,
            ),
        }
    # a hung phase must end as a failure inside the driver's limit, with
    # every thread's stack on stderr
    faulthandler.dump_traceback_later(1150, exit=True, file=sys.__stderr__)
    try:
        failed = run_phases(phases)
    finally:
        faulthandler.cancel_dump_traceback_later()
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
