"""Low-precision fast path tests (marker: lowprec).

Two int8 rungs (handyrl_tpu/models/quantize.py, docs/performance.md
§Low-precision fast path), each pinned against its fp32 reference:

* weights — per-channel symmetric int8 quantization of the serving/
  fleet/league engine params: round-trip error bounds, per-channel scale
  correctness, int8 residency through ``build_inference_model`` and the
  ``ModelRouter`` publish path (with publish-time MEASURED calibration),
  and the RecompileSentinel pin that flipping ``serving.weight_dtype``
  compiles each warm bucket at most once;

* observations — the int8 obs/wire plane: exact round-trip for the
  0/1-occupancy planes, generator-attached per-episode quant spec, and
  the acceptance bar inherited from the device-stage suite: a window
  sampled/assembled on device from int8-staged episodes must equal,
  key by key, the fp32 ``make_batch`` reference for the SAME episode,
  window start, and target player — with zero added host syncs.

Win-rate parity is MEASURED, never assumed: the slow leg pits the int8
engine against the fp32 engine holding identical params through the
league's ``PayoffMatrix`` ledger (the full |dwp| <= 0.03 / >= 400 games
bar needs a chip run and has no cell yet; the test leg plays fewer games
against a looser bound to keep CI honest without making it flaky).
"""

import random
import threading

import jax
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import InferenceModel, build_inference_model, init_variables
from handyrl_tpu.models.quantize import (
    QuantizedInferenceModel,
    calibration_batches_from_store,
    calibration_report,
    dequantize_leaf,
    dequantize_obs_tree,
    dequantize_params,
    has_quantized_leaves,
    is_quantized_leaf,
    obs_quant_spec,
    obs_tree_is_int8,
    param_bytes,
    quantize_leaf,
    quantize_obs_tree,
    quantize_params,
)
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.runtime.batch import make_batch
from handyrl_tpu.runtime.device_replay import DeviceEpisodeStage
from handyrl_tpu.runtime.generation import Generator
from handyrl_tpu.runtime.replay import EpisodeStore, decompress_block
from handyrl_tpu.utils import tree_map
from handyrl_tpu.utils.sanitizers import HostSyncSanitizer, RecompileSentinel

pytestmark = pytest.mark.lowprec


def _targs(env="TicTacToe", **over):
    base = {"mesh": {"dp": 1}}
    base.update(over)
    cfg = normalize_args({"env_args": {"env": env}, "train_args": base})
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    return args


def _gen_episodes(env_name, n, targs, seed=0):
    random.seed(seed)
    env = make_env({"env": env_name})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env, seed=seed))
    gen = Generator(env, targs)
    models = {p: model for p in env.players()}
    gen_args = {"player": env.players(), "model_id": {p: 1 for p in env.players()}}
    eps = []
    while len(eps) < n:
        ep = gen.generate(models, gen_args)
        if ep is not None:
            eps.append(ep)
    return env, module, eps


# ---------------------------------------------------------------------------
# weight quantization units
# ---------------------------------------------------------------------------


def test_quantize_leaf_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 32)).astype(np.float32) * 3.0
    node = quantize_leaf(w)
    assert is_quantized_leaf(node)
    assert node["int8_q"].dtype == np.int8
    assert node["int8_scale"].dtype == np.float32
    assert node["int8_scale"].shape == (32,)
    # symmetric codes: -128 unused
    assert node["int8_q"].min() >= -127
    # round-to-nearest: per-element error <= half a quantization step
    deq = dequantize_leaf(node)
    assert np.all(np.abs(deq - w) <= node["int8_scale"][None, :] / 2 + 1e-7)


def test_quantize_leaf_per_channel_scale_correctness():
    # hand-built per-OUT-channel absmax (flax puts out channels LAST)
    w = np.zeros((4, 3), np.float32)
    w[:, 0] = [0.5, -1.27, 0.1, 0.0]
    w[:, 1] = [2.0, 1.0, -2.54, 0.3]
    # column 2 all-zero: scale pins to 1.0 and codes to exact zeros
    node = quantize_leaf(w)
    np.testing.assert_allclose(
        node["int8_scale"], [1.27 / 127.0, 2.54 / 127.0, 1.0], rtol=1e-6
    )
    # the absmax element hits the full code range exactly
    assert node["int8_q"][1, 0] == -127
    assert node["int8_q"][2, 1] == -127
    assert np.all(node["int8_q"][:, 2] == 0)
    np.testing.assert_array_equal(dequantize_leaf(node)[:, 2], 0.0)

    # conv kernel layout (kh, kw, in, out): granule is still the last axis
    rng = np.random.default_rng(1)
    k = rng.standard_normal((3, 3, 4, 8)).astype(np.float32)
    knode = quantize_leaf(k)
    assert knode["int8_scale"].shape == (8,)
    np.testing.assert_allclose(
        knode["int8_scale"], np.abs(k).max(axis=(0, 1, 2)) / 127.0, rtol=1e-6
    )


def test_quantize_params_selective_and_idempotent():
    env = make_env({"env": "TicTacToe"})
    env.reset()
    module = env.net()
    params = init_variables(module, env, seed=3)["params"]

    q = quantize_params(params)
    assert has_quantized_leaves(q) and not has_quantized_leaves(params)

    n_kernels, n_small = [0], [0]

    def _walk(tree):
        if is_quantized_leaf(tree):
            n_kernels[0] += 1
            return
        if isinstance(tree, dict) or type(tree).__name__ == "FrozenDict":
            for v in tree.values():
                _walk(v)
            return
        # every unwrapped leaf is a small (< 2-d) fp32 tensor: biases and
        # norm params stay full precision by design
        assert np.asarray(tree).ndim < 2, np.asarray(tree).shape
        n_small[0] += 1

    _walk(q)
    assert n_kernels[0] > 0 and n_small[0] > 0

    # idempotent: re-quantizing a quantized tree is a no-op
    q2 = quantize_params(q)
    for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(q2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the byte shrink is the point: conv/dense-dominated nets land ~4x
    assert param_bytes(params) / param_bytes(q) > 3.0

    # dequantize restores an all-fp32 wrapper-free tree
    deq = dequantize_params(q)
    assert not has_quantized_leaves(deq)
    assert jax.tree.structure(deq) == jax.tree.structure(dict(params))


# ---------------------------------------------------------------------------
# engine build + router residency
# ---------------------------------------------------------------------------


def _tictactoe():
    env = make_env({"env": "TicTacToe"})
    env.reset()
    module = env.net()
    return env, module, env.observation(0)


def test_engine_build_int8_residency_and_fidelity():
    env, module, obs = _tictactoe()
    params = init_variables(module, env, seed=5)["params"]

    engine = build_inference_model(module, params, "int8")
    assert isinstance(engine, QuantizedInferenceModel)
    assert has_quantized_leaves(engine.variables["params"])
    with pytest.raises(ValueError, match="weight_dtype"):
        build_inference_model(module, params, "int4")

    fp32 = build_inference_model(module, params, "float32")
    assert isinstance(fp32, InferenceModel)

    batch = tree_map(lambda x: np.repeat(np.asarray(x)[None], 8, axis=0), obs)
    out_q = engine.inference_batch(batch)
    out_f = fp32.inference_batch(batch)
    for key, vf in out_f.items():
        if key == "hidden" or vf is None:
            continue
        np.testing.assert_allclose(
            np.asarray(out_q[key]), np.asarray(vf), atol=0.05
        )

    # the honest calibration record measures the same deviation
    rep = calibration_report(module, params, [batch])
    assert rep["calib_batches"] == 1.0
    assert 0.0 <= rep["calib_mean_dev"] <= rep["calib_max_dev"] <= 0.05


def test_router_publish_builds_int8_engine_and_calibrates(tmp_path):
    from handyrl_tpu.serving import ModelRouter

    env, module, obs = _tictactoe()
    params = init_variables(module, env, seed=7)["params"]
    cfg = {
        "port": 0, "max_models": 3, "slo_ms": 2000.0, "shed_policy": "none",
        "max_batch": 8, "max_wait_ms": 1.0, "warm_buckets": [1, 4],
        "queue_bound": 64, "recv_timeout": 0.0, "watch_interval": 0.0,
        "stats_interval": 0.0,
        "weight_dtype": "int8", "calibration_batches": 2,
    }
    router = ModelRouter(module, obs, cfg, model_dir=str(tmp_path))
    batch = tree_map(lambda x: np.repeat(np.asarray(x)[None], 4, axis=0), obs)
    router.calibration_source = lambda: [batch, batch]
    try:
        router.publish(1, params)
        mid, engine = router.resolve(1)
        assert mid == 1
        # the resident engine holds int8 params, not a dequantized copy
        assert has_quantized_leaves(engine.model.variables["params"])
        # publish-time calibration MEASURED against the provided batches
        assert router.last_calibration is not None
        assert router.last_calibration["calib_batches"] == 2.0
        assert router.last_calibration["calib_max_dev"] <= 0.05
        # the serialization template stays fp32 (int8 wrappers don't
        # round-trip flax serialization; cold resolve re-quantizes)
        assert not has_quantized_leaves(router._params_template())
    finally:
        router.stop()


def test_weight_dtype_flip_compiles_each_bucket_at_most_once():
    """The serving plane's warm-bucket contract survives the dtype knob:
    after the fp32 engine warmed buckets [1, 4], flipping to int8 costs
    at most one compile per bucket, and a second pass over BOTH engines
    and BOTH buckets is compile-free."""
    env, module, obs = _tictactoe()
    params = init_variables(module, env, seed=9)["params"]
    fp32 = build_inference_model(module, params, "float32")
    q = build_inference_model(module, params, "int8")

    def _batch(b):
        return tree_map(lambda x: np.repeat(np.asarray(x)[None], b, axis=0), obs)

    for b in (1, 4):  # fp32 warms its buckets first
        jax.block_until_ready(fp32.inference_batch_async(_batch(b)))

    with RecompileSentinel() as flip:
        for b in (1, 4):
            jax.block_until_ready(q.inference_batch_async(_batch(b)))
    assert flip.count <= 2, flip.report()

    with RecompileSentinel() as warm:
        for b in (1, 4):
            jax.block_until_ready(fp32.inference_batch_async(_batch(b)))
            jax.block_until_ready(q.inference_batch_async(_batch(b)))
    warm.assert_no_recompiles("weight_dtype flip, warm buckets")


# ---------------------------------------------------------------------------
# observation int8 plane
# ---------------------------------------------------------------------------


def test_obs_roundtrip_exact_for_01_planes():
    env, _, obs = _tictactoe()
    spec = obs_quant_spec(env, obs=obs)
    assert all(s == 1.0 and z == 0.0 for s, z in spec)

    q = quantize_obs_tree(obs, spec)
    assert obs_tree_is_int8(q)
    deq = dequantize_obs_tree(tree_map(jax.numpy.asarray, q), spec)
    for a, b in zip(jax.tree.leaves(deq), jax.tree.leaves(obs)):
        assert np.asarray(a).dtype == np.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_obs_nonunit_spec_roundtrip_and_validation():
    x = {"p": np.linspace(-1.0, 1.0, 32, dtype=np.float32).reshape(4, 8)}
    spec = [(2.0 / 254.0, 0.0)]
    q = quantize_obs_tree(x, spec)
    deq = dequantize_obs_tree(tree_map(jax.numpy.asarray, q), spec)
    assert np.max(np.abs(np.asarray(deq["p"]) - x["p"])) <= spec[0][0] / 2 + 1e-7

    class _BadEnv:
        def obs_int8_spec(self):
            return [(0.0, 0.0)]

    with pytest.raises(ValueError, match="scale"):
        obs_quant_spec(_BadEnv())


def test_generator_attaches_int8_obs_and_spec():
    targs_f = _targs("TicTacToe", compress_steps=4, forward_steps=4)
    targs_q = dict(targs_f, obs_int8=True)
    _, _, eps_f = _gen_episodes("TicTacToe", 3, targs_f, seed=17)
    _, _, eps_q = _gen_episodes("TicTacToe", 3, targs_q, seed=17)

    for ef, eq in zip(eps_f, eps_q):
        assert eq.get("obs_scale") is not None and eq.get("obs_zero") is not None
        spec = list(zip(
            np.asarray(eq["obs_scale"], np.float32).tolist(),
            np.asarray(eq["obs_zero"], np.float32).tolist(),
        ))
        assert ef["steps"] == eq["steps"]  # same seed -> same trajectory
        for bf, bq in zip(ef["blocks"], eq["blocks"]):
            of = decompress_block(bf)["obs"]
            oq = decompress_block(bq)["obs"]
            assert obs_tree_is_int8(oq) and not obs_tree_is_int8(of)
            deq = dequantize_obs_tree(oq, spec)
            for a, b in zip(jax.tree.leaves(deq), jax.tree.leaves(of)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the host-side calibration feed dequantizes back to the fp32 planes
    store = EpisodeStore(10)
    store.extend(eps_q)
    batches = calibration_batches_from_store(store, 2)
    assert len(batches) == 2
    assert not any(obs_tree_is_int8(b) for b in batches)


def test_int8_obs_ring_parity_vs_make_batch(monkeypatch):
    """The device-stage acceptance bar on the int8 plane: windows sampled
    and assembled ON DEVICE from int8-staged episodes equal, key by key,
    the fp32 ``make_batch`` reference for the same (episode, train_start,
    target player) — the int8 obs planes dequantize EXACTLY (0/1
    occupancy, scale 1.0 / zp 0), so the comparison is equality, not
    allclose-with-slack.  The sampled window dispatch is also pinned
    host-sync-free and recompile-free."""
    targs = _targs("HungryGeese", batch_size=8, forward_steps=8,
                   turn_based_training=False, observation=False,
                   obs_int8=True)
    env, module, eps = _gen_episodes("HungryGeese", 24, targs, seed=23)
    assert all(ep.get("obs_scale") is not None for ep in eps)
    mesh = make_mesh({"dp": 1})
    stage = DeviceEpisodeStage(module, targs, mesh, n_lanes=4, slots=256,
                               chunk_steps=8, track_episodes=True)
    for ep in eps:
        stage.add_episode(ep)
    stage.flush()
    stage.drain()

    replay = stage.replay
    # int8 residency: the ring's rows hold the obs planes as int8, a byte
    # an element (the row format says what each leaf is stored as)
    obs_fields = {k: f for k, f in replay.row_format.fields.items()
                  if k.startswith("obs") and k[3:].isdigit()}
    assert obs_fields and all(
        f.dtype == np.int8 and f.nbytes == np.prod(f.shape)
        for f in obs_fields.values()), obs_fields
    assert replay.rings["rec"].dtype == np.int32

    S = stage.slots
    G = int(jax.device_get(replay.rings["g"]))
    n = 16

    # warm the sampler, then pin the hot window clean
    first = replay.sample(jax.random.PRNGKey(2), n)
    jax.block_until_ready(jax.tree.leaves(first)[0])
    with HostSyncSanitizer() as sync, RecompileSentinel() as sentinel:
        warm = replay.sample(jax.random.PRNGKey(4), n)
    sync.assert_clean("int8 ring sample window")
    sentinel.assert_no_recompiles("int8 ring sample window")
    jax.block_until_ready(jax.tree.leaves(warm)[0])

    batch, info = replay.sample(jax.random.PRNGKey(3), n, with_info=True)
    batch = tree_map(np.asarray, batch)
    fwd, cs = targs["forward_steps"], targs["compress_steps"]

    checked = 0
    for i in range(n):
        lane, slot, player = (
            int(info["lane"][i]), int(info["slot"][i]), int(info["player"][i])
        )
        gs0 = G - 1 - ((G - 1 - slot) % S)
        hits = [s for s in stage.spans[lane] if s[0] <= gs0 <= s[1]]
        assert hits, f"sampled slot maps to no staged episode (lane {lane})"
        g0, _, ep = hits[0]
        train_start = gs0 - g0
        start = max(0, train_start - targs["burn_in_steps"])
        end = min(train_start + fwd, ep["steps"])
        first_block = start // cs
        last_block = (end - 1) // cs + 1
        window = {
            "args": ep["args"],
            "outcome": np.asarray(
                [ep["outcome"][p] for p in ep["players"]], np.float32
            ),
            "players": ep["players"],
            "blocks": ep["blocks"][first_block:last_block],
            "base": first_block * cs,
            "start": start, "end": end,
            "train_start": train_start, "total": ep["steps"],
        }
        if player >= 0:
            monkeypatch.setattr(
                "handyrl_tpu.runtime.batch.random.randrange", lambda _n: player
            )
        host = make_batch([window], targs)
        spec = list(zip(
            np.asarray(ep["obs_scale"], np.float32).tolist(),
            np.asarray(ep["obs_zero"], np.float32).tolist(),
        ))
        for key in host:
            hval = host[key]
            if key == "observation":
                assert obs_tree_is_int8(hval)  # int8 end-to-end on the host path
                hval = dequantize_obs_tree(
                    tree_map(jax.numpy.asarray, hval), spec)
            for hleaf, dleaf in zip(
                jax.tree.leaves(hval), jax.tree.leaves(batch[key])
            ):
                np.testing.assert_array_equal(
                    np.asarray(dleaf)[i], np.asarray(hleaf)[0],
                    err_msg=f"window {i} key {key}",
                )
        checked += 1
    assert checked == n


def test_int8_obs_train_step_matches_fp32():
    """forward/backward parity through the real train step: the SAME
    seeded trajectories encoded fp32 vs int8 must produce bit-equal
    observations after in-graph dequantize, and the int8-fed train step
    must run to a finite loss."""
    over = dict(batch_size=4, forward_steps=4, compress_steps=4)
    targs_f = _targs("TicTacToe", **over)
    targs_q = dict(_targs("TicTacToe", obs_int8=True, **over))
    env, module, eps_f = _gen_episodes("TicTacToe", 6, targs_f, seed=31)
    _, _, eps_q = _gen_episodes("TicTacToe", 6, targs_q, seed=31)

    store_f, store_q = EpisodeStore(20), EpisodeStore(20)
    store_f.extend(eps_f)
    store_q.extend(eps_q)

    random.seed(7)
    wins_f = [store_f.sample_window(targs_f["forward_steps"],
                                    targs_f["burn_in_steps"],
                                    targs_f["compress_steps"])
              for _ in range(4)]
    random.seed(7)
    wins_q = [store_q.sample_window(targs_q["forward_steps"],
                                    targs_q["burn_in_steps"],
                                    targs_q["compress_steps"])
              for _ in range(4)]
    batch_f = make_batch(wins_f, targs_f)
    batch_q = make_batch(wins_q, targs_q)
    assert obs_tree_is_int8(batch_q["observation"])

    env.reset()
    targs_q["_obs_quant"] = obs_quant_spec(env, obs=env.observation(0))
    params = init_variables(module, env, seed=13)["params"]

    from handyrl_tpu.parallel.train_step import forward_prediction

    out_f = forward_prediction(
        module, params, tree_map(jax.numpy.asarray, batch_f), targs_f)
    out_q = forward_prediction(
        module, params, tree_map(jax.numpy.asarray, batch_q), targs_q)
    for key, vf in out_f.items():
        if vf is None:
            continue
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(vf)),
            np.asarray(jax.device_get(out_q[key])), err_msg=key)

    ctx = TrainContext(module, targs_q, make_mesh({"dp": 1}))
    state = ctx.init_state(params)
    state, metrics = ctx.train_step(state, ctx.put_batch(batch_q), 1e-4)
    assert np.isfinite(float(jax.device_get(metrics["total"])))


# ---------------------------------------------------------------------------
# slow legs: measured win-rate parity + bf16 compute e2e
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_wp_parity_int8_vs_fp32_pit():
    """MEASURED parity: the int8 engine pits against the fp32 engine
    holding IDENTICAL params, seat-balanced through the PayoffMatrix
    ledger.  The test leg plays 64 games against a generous bound (the
    binomial noise floor at 64 games is ~0.13 at 2 sigma); the full
    >= 400-game |dwp| <= 0.03 bar needs a chip run and has no cell yet."""
    from handyrl_tpu.agents import Agent
    from handyrl_tpu.league.matchmaker import PayoffMatrix
    from handyrl_tpu.runtime.evaluation import evaluate_mp

    env, module, _ = _tictactoe()
    params = init_variables(module, env, seed=21)["params"]
    a_q = Agent(build_inference_model(module, params, "int8"),
                temperature=1.0, seed=11)
    a_f = Agent(build_inference_model(module, params, "float32"),
                temperature=1.0, seed=12)
    results = evaluate_mp({"env": "TicTacToe"}, {0: a_q, 1: a_f},
                          64, num_workers=2)
    payoff = PayoffMatrix()
    for _pat, res in results.items():
        for outcome, count in res.items():
            payoff.record_score("int8", "fp32", float(outcome),
                                -float(outcome), n=count)
    wp = payoff.win_points("int8", "fp32")
    assert payoff.games("int8", "fp32") == 64
    assert abs(wp - 0.5) <= 0.2, (
        f"int8 vs fp32 wp {wp} over 64 games — far outside sampling noise; "
        "quantization is changing the policy"
    )


@pytest.mark.slow
def test_bf16_compute_e2e_trains_clean(tmp_path, monkeypatch):
    """compute_dtype: bfloat16 end to end: bf16 forward/backward over
    fp32 master params trains through the full Learner stack to a finite
    loss with ZERO divergence-sentinel skips — the knob changes compute
    width, not training health."""
    import json

    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    cfg = normalize_args({
        "env_args": {"env": "TicTacToe"},
        "train_args": {
            "compute_dtype": "bfloat16",
            "batch_size": 8,
            "forward_steps": 4,
            "compress_steps": 4,
            "minimum_episodes": 8,
            "update_episodes": 16,
            "maximum_episodes": 500,
            "epochs": 2,
            "eval_rate": 0.0,
            "mesh": {"dp": 1},
            "worker": {"num_parallel": 1},
        },
    })
    learner = Learner(cfg)
    learner.run()

    records = [json.loads(l) for l in open("metrics.jsonl")]
    trained = [r for r in records if r.get("loss") is not None]
    assert trained, "no trained epoch recorded a loss"
    for r in trained:  # loss is the per-component dict: pin the total
        assert np.isfinite(float(r["loss"]["total"])), r["loss"]
    assert records[-1]["steps"] > 0
    assert sum(r.get("sentinel_skipped_steps", 0) for r in records) == 0
