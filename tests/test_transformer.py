"""Transformer (KV-cache memory) model family tests: step semantics,
memory behavior, engine/export compatibility, and the full training path
through the recurrent lax.scan hidden-carry machinery.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import InferenceModel, TransformerNet, init_variables


def _model(env_args):
    env = make_env(env_args)
    module = env.net()
    variables = init_variables(module, env)
    return env, module, InferenceModel(module, variables)


def test_transformer_step_and_memory():
    env, module, model = _model({"env": "TicTacToe", "net": "transformer"})
    assert isinstance(module, TransformerNet)
    env.reset()
    obs = env.observation(0)

    hidden = model.init_hidden()
    assert float(hidden["pos"]) == 0.0
    out1 = model.inference(obs, hidden)
    assert out1["policy"].shape == (9,)
    assert -1.0 <= float(out1["value"][0]) <= 1.0
    h1 = out1["hidden"]
    assert float(h1["pos"]) == 1.0
    # a cache slot was written
    assert np.abs(np.asarray(h1["layers"][0]["k"])).sum() > 0

    # memory matters: the same query with a DIFFERENT history step differs
    # (history must contain distinct content, else all cached values match)
    env.play(4)
    obs2 = env.observation(0)
    out_fresh = model.inference(obs2, model.init_hidden())
    out_mem = model.inference(obs2, h1)  # h1 remembers the empty board
    assert not np.allclose(out_fresh["policy"], out_mem["policy"], atol=1e-4)


def test_transformer_net_args_override():
    """env_args['net_args'] scales the family without a new env subclass
    (the benchmark's xfmr_d1536 configuration and the scale configs rely on this)."""
    env, module, model = _model({
        "env": "Geister", "net": "transformer",
        "net_args": {"d_model": 32, "n_heads": 2, "n_layers": 3,
                     "memory_len": 8},
    })
    assert isinstance(module, TransformerNet)
    assert (module.d_model, module.n_heads, module.n_layers,
            module.memory_len) == (32, 2, 3, 8)
    assert module.with_return  # env's spec survives the merge
    env.reset()
    out = model.inference(env.observation(0), model.init_hidden())
    assert out["policy"].shape == (env.action_size(),)
    assert len(out["hidden"]["layers"]) == 3


def test_stateful_model_without_observation_fails_fast():
    """A recurrent/memory model with observation: false must be rejected
    at TrainContext construction (clear startup error), not crash a
    learner thread mid-training on batch shapes (found by driving
    main.py --train with a transformer config missing the flag)."""
    from handyrl_tpu.parallel import TrainContext, make_mesh

    cfg = normalize_args(
        {
            "env_args": {"env": "TicTacToe", "net": "transformer"},
            "train_args": {"batch_size": 8, "forward_steps": 4},
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    assert not args.get("observation")
    env = make_env(args["env"])
    with pytest.raises(ValueError, match="observation: true"):
        TrainContext(env.net(), args, make_mesh(args["mesh"]))


def test_smoke_transformer_config_traces():
    """Abstractly evaluate the train program chip_smoke.py's transformer
    phase and the benchmark's ``xfmr_train_t64`` cell compile on the chip
    (whatever chip_smoke.TRANSFORMER_TPU_NET_ARGS pins: d1536/L8/H16, B64,
    T64, bf16, einsum attention; the flash path's kernel shapes are covered
    in tests/test_flash_attention.py and tests/test_chip_compile.py).
    Neither executes in CI, so without this trace a shape bug in the big
    config would first surface on the chip.  eval_shape runs the full
    trace — forward, attention, losses, grads, Adam — without lowering or
    allocating the big-net state."""
    import chip_smoke
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch
    from handyrl_tpu.models import RandomModel
    from handyrl_tpu.utils import tree_map

    cfg = normalize_args(
        {
            "env_args": {"env": "Geister", "net": "transformer",
                         "net_args": chip_smoke.TRANSFORMER_TPU_NET_ARGS},
            "train_args": dict(chip_smoke.TRANSFORMER_TPU_OVERRIDES),
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    env = make_env(args["env"])
    module = env.net()
    # derived from the pin, not hard-coded: the guard traces whatever the
    # chip-gated phase will compile
    assert (module.d_model, module.n_layers) == (
        chip_smoke.TRANSFORMER_TPU_NET_ARGS["d_model"],
        chip_smoke.TRANSFORMER_TPU_NET_ARGS["n_layers"],
    )

    # abstract params/opt state: no 134M-param allocation
    env.reset()
    obs_b = tree_map(lambda x: jnp.asarray(np.asarray(x))[None], env.observation(0))
    var_shape = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), obs_b, module.initial_state((1,))
    )
    mesh = make_mesh({"dp": -1})
    ctx = TrainContext(module, args, mesh)
    state_shape = jax.eval_shape(
        lambda p: {"params": p, "opt_state": ctx.tx.init(p),
                   "steps": jnp.zeros((), jnp.int32)},
        var_shape["params"],
    )

    # a real batch at the exact stage geometry (windows resampled from a
    # couple of random games — shapes are what matter here); the
    # RandomModel spec is written out directly so nothing compiles or
    # allocates the big net on the CPU test backend
    small = make_env(args["env"])
    small.reset()
    A = small.action_size()
    rm = RandomModel({"policy": ((A,), np.float32),
                      "value": ((1,), np.float32),
                      "return": ((1,), np.float32)})
    store = EpisodeStore(64)
    gen = Generator(small, args)
    gen_args = {"player": small.players(), "model_id": {p: 0 for p in small.players()}}
    while len(store) < 2:
        ep = gen.generate({p: rm for p in small.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(args["forward_steps"], args["burn_in_steps"],
                                args["compress_steps"])
        if w is not None:
            windows.append(w)
    batch = make_batch(windows, args)
    assert batch["action"].shape[:3] == (64, 64, 2)

    new_state, metrics = jax.eval_shape(
        ctx._step_fn, state_shape, batch,
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    # donation compatibility: the updated state must mirror the input layout
    assert jax.tree.structure(new_state) == jax.tree.structure(state_shape)
    chex = [(a.shape, a.dtype) for a in jax.tree.leaves(new_state)]
    want = [(a.shape, a.dtype) for a in jax.tree.leaves(state_shape)]
    assert chex == want
    assert set(metrics) >= {"p", "v", "ent", "total", "dcnt"}


def test_transformer_long_t1024_pin_traces():
    """Abstractly evaluate the longest-T program the pins describe
    (chip_smoke.TRANSFORMER_LONG_TPU): T1024 x d1536 x L8, flash kernel
    auto-picked (T >= flash_min_t), remat 'block' (what 'auto' resolves to
    on TPU at this T), bf16 compute.  Same contract as
    test_smoke_transformer_config_traces: nothing runs this shape in CI,
    so this trace is what keeps a shape bug from first surfacing on the
    chip."""
    import chip_smoke
    from handyrl_tpu.models import RandomModel
    from handyrl_tpu.parallel import TrainContext, make_mesh, resolve_seq_attention
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch
    from handyrl_tpu.utils import tree_map

    pins = chip_smoke.TRANSFORMER_LONG_TPU
    T = pins["sweep_t"][-1]
    B = pins["batch_by_t"][T]
    cfg = normalize_args(
        {
            "env_args": {"env": "Geister", "net": "transformer",
                         "net_args": pins["net_args"]},
            "train_args": {
                "batch_size": B, "burn_in_steps": 0, "forward_steps": T,
                "observation": True, "seq_attention": "auto",
                "flash_min_t": pins["flash_min_t"],
                "compute_dtype": pins["compute_dtype"],
                "remat": "block",
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    assert resolve_seq_attention(args, T) == "flash"

    env = make_env(args["env"])
    module = env.net()
    assert module.d_model == pins["net_args"]["d_model"]
    env.reset()
    obs_b = tree_map(lambda x: jnp.asarray(np.asarray(x))[None], env.observation(0))
    var_shape = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), obs_b, module.initial_state((1,))
    )
    ctx = TrainContext(module, args, make_mesh({"dp": -1}))
    state_shape = jax.eval_shape(
        lambda p: {"params": p, "opt_state": ctx.tx.init(p),
                   "steps": jnp.zeros((), jnp.int32)},
        var_shape["params"],
    )

    small = make_env(args["env"])
    small.reset()
    A = small.action_size()
    rm = RandomModel({"policy": ((A,), np.float32),
                      "value": ((1,), np.float32),
                      "return": ((1,), np.float32)})
    store = EpisodeStore(16)
    gen = Generator(small, args)
    gen_args = {"player": small.players(), "model_id": {p: 0 for p in small.players()}}
    while len(store) < 2:
        ep = gen.generate({p: rm for p in small.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(args["forward_steps"], args["burn_in_steps"],
                                args["compress_steps"])
        if w is not None:
            windows.append(w)
    batch = make_batch(windows, args)
    assert batch["action"].shape[:3] == (B, T, 2)

    new_state, metrics = jax.eval_shape(
        ctx._step_fn, state_shape, batch,
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    got = [(a.shape, a.dtype) for a in jax.tree.leaves(new_state)]
    want = [(a.shape, a.dtype) for a in jax.tree.leaves(state_shape)]
    assert got == want
    assert set(metrics) >= {"p", "v", "ent", "total", "dcnt"}


def test_transformer_ring_wraparound():
    env, module, model = _model({"env": "TicTacToe", "net": "transformer"})
    env.reset()
    obs = env.observation(0)
    hidden = model.init_hidden()
    for _ in range(module.memory_len + 5):  # past the ring size
        out = model.inference(obs, hidden)
        hidden = out["hidden"]
    assert float(hidden["pos"]) == module.memory_len + 5
    assert np.isfinite(np.asarray(out["policy"])).all()


def test_transformer_through_inference_engine():
    from handyrl_tpu.runtime import BatchedInferenceEngine

    env, module, model = _model({"env": "TicTacToe", "net": "transformer"})
    env.reset()
    obs = env.observation(0)
    engine = BatchedInferenceEngine(model, max_batch=4).start()
    client = engine.client()
    direct = model.inference(obs, model.init_hidden())
    via_engine = client.inference(obs, None)  # None -> initial state slice
    engine.stop()
    np.testing.assert_allclose(via_engine["policy"], direct["policy"], rtol=2e-4, atol=2e-5)


def test_transformer_export_roundtrip(tmp_path):
    from handyrl_tpu.models import ExportedModel, export_model

    env, module, model = _model({"env": "TicTacToe", "net": "transformer"})
    env.reset()
    obs = env.observation(0)
    path = str(tmp_path / "ttt_tf.hlo")
    export_model(module, model.variables, obs, path)
    ex = ExportedModel(path)
    o1 = model.inference(obs, model.init_hidden())
    o2 = ex.inference(obs, ex.init_hidden())
    np.testing.assert_allclose(o1["policy"], o2["policy"], rtol=1e-4, atol=1e-5)


def _transformer_batch(env_name, burn_in=2, forward_steps=4, batch_size=8,
                       net_args=None, train_over=None):
    from handyrl_tpu.models import RandomModel
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    env_args = {"env": env_name, "net": "transformer"}
    if net_args:
        env_args["net_args"] = net_args
    cfg = normalize_args(
        {
            "env_args": env_args,
            "train_args": {
                "batch_size": batch_size,
                "forward_steps": forward_steps,
                "burn_in_steps": burn_in,
                "compress_steps": 4,
                "observation": True,
                **(train_over or {}),
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    env = make_env(args["env"])
    module = env.net()
    variables = init_variables(module, env)
    model = InferenceModel(module, variables)
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))
    store = EpisodeStore(64)
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < 6:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(args["forward_steps"], args["burn_in_steps"], args["compress_steps"])
        if w is not None:
            windows.append(w)
    return env, module, variables, make_batch(windows, args), args


def test_transformer_seq_path_matches_scan():
    """The whole-window attention path must equal the KV-cache scan path —
    in values AND in parameter gradients (burn-in stop_gradient included)."""
    from handyrl_tpu.parallel import forward_prediction

    env, module, variables, batch, args = _transformer_batch("TicTacToe")
    batch = jax.tree.map(jax.numpy.asarray, batch)
    out_seq = forward_prediction(module, variables["params"], batch, {**args, "seq_forward": True})
    out_scan = forward_prediction(module, variables["params"], batch, {**args, "seq_forward": False})
    assert set(out_seq) == set(out_scan)
    for k in out_seq:
        np.testing.assert_allclose(
            np.asarray(out_seq[k]), np.asarray(out_scan[k]), rtol=2e-4, atol=2e-4
        )

    def loss(params, seq_forward):
        # realistic downstream use: softmax over action-masked logits (the
        # raw logits carry -1e32 mask values; squaring those is numeric noise)
        outs = forward_prediction(module, params, batch, {**args, "seq_forward": seq_forward})
        p = jax.nn.softmax(outs["policy"], axis=-1)
        rest = sum((v ** 2).sum() for k, v in outs.items() if k != "policy")
        return (p ** 2).sum() + rest

    g_seq = jax.grad(lambda p: loss(p, True))(variables["params"])
    g_scan = jax.grad(lambda p: loss(p, False))(variables["params"])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4
        ),
        g_seq,
        g_scan,
    )


def test_resolve_seq_attention_policy():
    """The auto-pick policy, in one place: einsum below flash_min_t, the
    Pallas kernel at and above it, explicit modes pass through."""
    from handyrl_tpu.parallel import resolve_seq_attention, resolve_seq_remat

    args = {"seq_attention": "auto", "flash_min_t": 128}
    assert resolve_seq_attention(args, 64) == "einsum"
    assert resolve_seq_attention(args, 127) == "einsum"
    assert resolve_seq_attention(args, 128) == "flash"
    assert resolve_seq_attention(args, 1024) == "flash"
    for mode in ("einsum", "flash", "ring"):
        assert resolve_seq_attention({"seq_attention": mode}, 8) == mode
    # remat rungs: ladder strings pass through, booleans collapse, auto
    # is 'none' off-TPU (this suite runs on CPU)
    assert resolve_seq_remat({"remat": "attn"}, 1024) == "attn"
    assert resolve_seq_remat({"remat": True}, 8) == "block"
    assert resolve_seq_remat({"remat": False}, 4096) == "none"
    assert resolve_seq_remat({"remat": "auto"}, 4096) == "none"
    # ring attention never composes with the ladder: the ring partitions
    # activation memory itself, and checkpoint-around-shard_map fails
    assert resolve_seq_remat(
        {"remat": "auto", "seq_attention": "ring"}, 4096
    ) == "none"


def test_seq_remat_bit_parity():
    """The remat ladder must not change the math at a T64 window: the
    jitted LOSS is bit-identical across remat none/attn/block, and
    parameter gradients agree to float-reassociation precision (the
    checkpoint's optimization barriers change XLA's fusion of the
    backward, so reductions reassociate at the ~1e-9 level — same ops,
    same inputs, different summation order; anything larger would be a
    real semantics change)."""
    from handyrl_tpu.parallel import forward_prediction

    env, module, variables, batch, args = _transformer_batch(
        "TicTacToe", burn_in=2, forward_steps=62,
        # small width keeps the three T64 jit compiles cheap; the ladder's
        # structure (per-block checkpoints, qkv tags) is width-independent
        net_args={"d_model": 32, "n_heads": 2, "n_layers": 2, "memory_len": 16},
    )
    batch = jax.tree.map(jax.numpy.asarray, batch)

    def loss(params, remat):
        outs = forward_prediction(
            module, params, batch, {**args, "seq_forward": True, "remat": remat}
        )
        p = jax.nn.softmax(outs["policy"], axis=-1)
        rest = sum((v ** 2).sum() for k, v in outs.items() if k != "policy")
        return (p ** 2).sum() + rest

    # none vs block is the acceptance pair (the 'attn' rung sits between
    # them structurally and rides the slow-leg memory test); two T64
    # compiles keep this inside the tier-1 budget
    vg = {
        remat: jax.jit(jax.value_and_grad(lambda p, r=remat: loss(p, r)))(
            variables["params"]
        )
        for remat in ("none", "block")
    }
    base_l, base_g = vg["none"]
    for remat in ("block",):
        l, g = vg[remat]
        # bit-identical on this container's jaxlib; the rtol guard keeps a
        # future XLA that fuses the checkpointed forward differently from
        # turning a last-ulp reassociation into a spurious CI failure
        np.testing.assert_allclose(
            np.asarray(l), np.asarray(base_l), rtol=1e-7, err_msg=remat
        )
        for a, b in zip(jax.tree.leaves(base_g), jax.tree.leaves(g)):
            # atol floor: near-zero bias grads are pure cancellation noise
            # (magnitudes ~1e-8), where reassociation moves them ~1e-7
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6, err_msg=remat
            )


@pytest.mark.parametrize("remat", ["none", "attn", "block"])
def test_sections_sum_their_own_gradient(remat):
    """``sum_grads`` given, the seq forward runs as custom-VJP sections that
    hand their own parameter cotangents to it: every parameter exactly
    once (a function that doubles shows it), the loss untouched, on every
    rung of the remat ladder."""
    from handyrl_tpu.parallel import forward_prediction

    env, module, variables, batch, args = _transformer_batch(
        "TicTacToe", burn_in=2, forward_steps=6,
        net_args={"d_model": 32, "n_heads": 2, "n_layers": 2, "memory_len": 8},
    )
    batch = jax.tree.map(jax.numpy.asarray, batch)
    seen = []

    def doubled(tree, x_ct, token):
        seen.extend(jax.tree.leaves(tree))
        return jax.tree.map(lambda g: 2.0 * g, tree), x_ct, token

    def loss(params, sum_grads):
        outs = forward_prediction(
            module, params, batch, {**args, "seq_forward": True, "remat": remat}, sum_grads
        )
        return sum((v ** 2).sum() for v in outs.values())

    plain_l, plain_g = jax.jit(jax.value_and_grad(lambda p: loss(p, None)))(variables["params"])
    own_l, own_g = jax.jit(jax.value_and_grad(lambda p: loss(p, doubled)))(variables["params"])
    np.testing.assert_allclose(np.asarray(own_l), np.asarray(plain_l), rtol=1e-6)
    assert len(seen) == len(jax.tree.leaves(variables["params"]))
    for a, b in zip(jax.tree.leaves(plain_g), jax.tree.leaves(own_g)):
        np.testing.assert_allclose(np.asarray(b), 2.0 * np.asarray(a), rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_seq_remat_reduces_peak_memory():
    """The point of the ladder: at a long window the checkpointed blocks
    compile to a measurably smaller peak (XLA compiled memory analysis —
    temp bytes) than remat 'none'.  T1024 x 4 layers of einsum attention
    keeps 4 (B, H, T, T) score/softmax slabs live without remat; 'block'
    keeps block inputs + the tagged q/k/v only.  Slow leg: three T1024
    XLA:CPU compiles (~90 s on a 2-core host)."""
    module = TransformerNet(
        num_actions=4, d_model=64, n_heads=2, n_layers=4, memory_len=64
    )
    B, T = 1, 1024
    obs = jnp.zeros((B, T, 8), jnp.float32)
    km = jnp.ones((B, T), jnp.float32)
    params = module.init(
        jax.random.PRNGKey(0), obs, None, seq=True, key_mask=km
    )["params"]

    def temp_bytes(remat):
        def loss(p):
            out = module.apply(
                {"params": p}, obs, None, seq=True, key_mask=km, remat=remat
            )
            return (out["policy"] ** 2).sum() + (out["value"] ** 2).sum()

        lowered = jax.jit(jax.grad(loss)).lower(params)
        return int(lowered.compile().memory_analysis().temp_size_in_bytes)

    none_b, attn_b, block_b = (temp_bytes(r) for r in ("none", "attn", "block"))
    # each rung must buy real memory: 'attn' strictly below 'none', and
    # 'block' at least 25% below (XLA keeps the transient forward slabs
    # either way, so the saving here is the per-layer residual set — the
    # margin grows with n_layers on the production 8-layer pin)
    assert attn_b < none_b, (none_b, attn_b)
    assert block_b < 0.75 * none_b, (none_b, block_b)


@pytest.mark.slow
def test_long_context_train_step_t1024_d1536():
    """The acceptance shape: a T1024 x d1536 train step compiles AND steps
    under the remat ladder on the CPU mesh, with the remat-none peak
    measured (never executed — that is the OOM-by-construction program at
    production batch sizes) strictly above the ladder's."""
    from handyrl_tpu.parallel import TrainContext, make_mesh

    env, module, variables, batch, args = _transformer_batch(
        "TicTacToe", burn_in=0, forward_steps=1024, batch_size=2,
        net_args={"d_model": 1536, "n_heads": 16, "n_layers": 2,
                  "memory_len": 64},
        train_over={"seq_attention": "einsum", "remat": "block",
                    "mesh": {"dp": 1}},
    )
    mesh = make_mesh({"dp": 1})
    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(variables["params"])
    device_batch = ctx.put_batch(batch)

    def peak(ctx_, state_, batch_):
        lowered = ctx_._bind(state_).lower(
            state_, batch_, jax.ShapeDtypeStruct((), jnp.float32)
        )
        return int(lowered.compile().memory_analysis().temp_size_in_bytes)

    ctx_none = TrainContext(module, dict(args, remat="none"), mesh)
    state_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    batch_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), device_batch
    )
    peak_block = peak(ctx, state_shapes, batch_shapes)
    peak_none = peak(ctx_none, state_shapes, batch_shapes)
    assert peak_block < peak_none, (peak_block, peak_none)

    state, metrics = ctx.train_step(state, device_batch, 1e-4)
    assert np.isfinite(float(jax.device_get(metrics["total"])))


@pytest.mark.parametrize("env_name", ["TicTacToe", "Geister"])
def test_transformer_train_step(env_name):
    """Full sharded train step through the scan/burn-in recurrent path."""
    from handyrl_tpu.models import RandomModel
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    cfg = normalize_args(
        {
            "env_args": {"env": env_name, "net": "transformer"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 4,
                "burn_in_steps": 2,
                "compress_steps": 4,
                "observation": True,  # recurrent path needs full-player batches
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]

    env = make_env(args["env"])
    module = env.net()
    variables = init_variables(module, env)
    model = InferenceModel(module, variables)
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))

    store = EpisodeStore(64)
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < 6:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(args["forward_steps"], args["burn_in_steps"], args["compress_steps"])
        if w is not None:
            windows.append(w)
    batch = make_batch(windows, args)

    ctx = TrainContext(module, args, make_mesh({"dp": -1}))
    state = ctx.init_state(variables["params"])
    state, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
    assert np.isfinite(float(jax.device_get(metrics["total"])))


def test_transformer_train_step_tensor_parallel():
    """The transformer's Dense kernels under an 'mp' mesh axis: the same
    batch + params on a dp x mp mesh must produce the same update metrics
    as the dp-only run (GSPMD inserts the tp gathers; shape-based kernel
    sharding from parallel/mesh.py applies to the attention/MLP Dense
    layers exactly as to conv kernels)."""
    from handyrl_tpu.models import RandomModel
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    cfg = normalize_args(
        {
            "env_args": {"env": "TicTacToe", "net": "transformer"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 4,
                "burn_in_steps": 2,
                "compress_steps": 4,
                "observation": True,
                "seq_attention": "einsum",
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]

    env = make_env(args["env"])
    module = env.net()
    variables = init_variables(module, env)
    model = InferenceModel(module, variables)
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))

    store = EpisodeStore(64)
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < 6:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(
            args["forward_steps"], args["burn_in_steps"], args["compress_steps"]
        )
        if w is not None:
            windows.append(w)
    batch = make_batch(windows, args)

    metrics_by_mesh = {}
    for name, mesh_spec in [("dp", {"dp": 4}), ("dpmp", {"dp": 4, "mp": 2})]:
        ctx = TrainContext(module, args, make_mesh(mesh_spec))
        state = ctx.init_state(variables["params"])
        _, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
        metrics_by_mesh[name] = {
            k: float(jax.device_get(v)) for k, v in metrics.items()
        }
    assert np.isfinite(metrics_by_mesh["dpmp"]["total"])
    for k in ("total", "p", "v", "dcnt"):
        np.testing.assert_allclose(
            metrics_by_mesh["dpmp"][k], metrics_by_mesh["dp"][k],
            rtol=1e-4, atol=1e-6, err_msg=k,
        )


def test_transformer_train_step_ring_sp():
    """seq_attention='ring': the FULL train step on a dp x sp mesh with the
    transformer window sharded across the 'sp' axis — metrics must match
    the einsum path on the same mesh AND the single-chip einsum step (the
    dp x sp composition changes the program layout, not the semantics).
    Real pass on this container's jax 0.4.37 via the _ring_loop compat
    ladder (identity marking on pre-VMA jax)."""
    from handyrl_tpu.models import RandomModel
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    cfg = normalize_args(
        {
            "env_args": {"env": "TicTacToe", "net": "transformer"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 8,  # T = 8, divisible by sp = 4
                "burn_in_steps": 0,
                "compress_steps": 4,
                "observation": True,
                "seq_forward": True,
                "mesh": {"dp": 2, "sp": 4},
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]

    env = make_env(args["env"])
    module = env.net()
    variables = init_variables(module, env)
    model = InferenceModel(module, variables)
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))

    store = EpisodeStore(64)
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < 6:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(args["forward_steps"], args["burn_in_steps"], args["compress_steps"])
        if w is not None:
            windows.append(w)
    batch = make_batch(windows, args)

    mesh = make_mesh(args["mesh"])
    results = {}
    for mode in ("einsum", "ring"):
        ctx = TrainContext(module, {**args, "seq_attention": mode}, mesh)
        state = ctx.init_state(variables["params"])
        state, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
        results[mode] = jax.device_get(metrics)
    # the single-chip einsum step: same params/batch, no mesh axes at all
    ctx1 = TrainContext(
        module, {**args, "seq_attention": "einsum", "mesh": {"dp": 1}},
        make_mesh({"dp": 1}),
    )
    state = ctx1.init_state(variables["params"])
    _, metrics = ctx1.train_step(state, ctx1.put_batch(batch), 1e-4)
    results["single_chip"] = jax.device_get(metrics)
    for k in ("total", "p", "v", "dcnt"):
        np.testing.assert_allclose(
            results["ring"][k], results["einsum"][k], rtol=2e-4, atol=2e-5
        )
        # bf16-tolerance bound vs the single chip (the acceptance bar);
        # everything here runs fp32 so the observed gap is far tighter
        np.testing.assert_allclose(
            results["ring"][k], results["single_chip"][k], rtol=8e-3, atol=1e-4
        )


def test_ring_mode_requires_sp_axis():
    """seq_attention='ring' without an 'sp' mesh axis fails loudly at
    CONFIG time (normalize_args), and the same guard still fires at
    TrainContext construction for direct-API callers who skip the config
    layer — never deep inside the first traced step."""
    from handyrl_tpu.parallel import TrainContext, make_mesh

    with pytest.raises(ValueError, match="sp"):
        normalize_args(
            {
                "env_args": {"env": "TicTacToe", "net": "transformer"},
                "train_args": {"seq_attention": "ring", "batch_size": 8},
            }
        )
    cfg = normalize_args(
        {"env_args": {"env": "TicTacToe", "net": "transformer"},
         "train_args": {"batch_size": 8}}
    )
    args = dict(cfg["train_args"], seq_attention="ring", observation=True)
    args["env"] = cfg["env_args"]
    env = make_env(args["env"])
    with pytest.raises(ValueError, match="sp"):
        TrainContext(env.net(), args, make_mesh({"dp": -1}))


def test_ring_mode_requires_divisible_window():
    from handyrl_tpu.parallel import TrainContext, make_mesh

    raw_train = {
        "seq_attention": "ring", "batch_size": 8,
        "forward_steps": 10, "mesh": {"dp": 2, "sp": 4},
    }
    with pytest.raises(ValueError, match="divisible"):
        normalize_args(
            {"env_args": {"env": "TicTacToe", "net": "transformer"},
             "train_args": raw_train}
        )
    cfg = normalize_args(
        {"env_args": {"env": "TicTacToe", "net": "transformer"},
         "train_args": {**raw_train, "forward_steps": 12}}
    )
    args = dict(cfg["train_args"], forward_steps=10, observation=True)
    args["env"] = cfg["env_args"]
    env = make_env(args["env"])
    with pytest.raises(ValueError, match="divisible"):
        TrainContext(env.net(), args, make_mesh(args["mesh"]))


def test_attn_mode_alias_and_knob_validation():
    """attn_mode aliases seq_attention; blk/remat/mesh knobs are validated
    loudly at config time (the PR 6 fail-at-startup pattern)."""
    cfg = normalize_args(
        {"env_args": {"env": "TicTacToe", "net": "transformer"},
         "train_args": {"attn_mode": "flash"}}
    )
    assert cfg["train_args"]["seq_attention"] == "flash"
    assert "attn_mode" not in cfg["train_args"]
    base = {"env_args": {"env": "TicTacToe"}}
    with pytest.raises(ValueError, match="alias"):
        normalize_args(
            {**base, "train_args": {"attn_mode": "flash", "seq_attention": "einsum"}}
        )
    with pytest.raises(ValueError, match="blk_q"):
        normalize_args({**base, "train_args": {"blk_q": 96}})
    with pytest.raises(ValueError, match="power of two"):
        normalize_args({**base, "train_args": {"blk_k": 4}})
    with pytest.raises(ValueError, match="remat"):
        normalize_args({**base, "train_args": {"remat": "everything"}})
    # bare ints are rejected: 1 == True under tuple membership, but the
    # isinstance-based resolver would read it as 'auto' — refuse the
    # ambiguity at config time
    with pytest.raises(ValueError, match="remat"):
        normalize_args({**base, "train_args": {"remat": 1}})
    with pytest.raises(ValueError, match="mesh"):
        normalize_args({**base, "train_args": {"mesh": {"dp": -1, "sp": -1}}})
    with pytest.raises(ValueError, match="mesh"):
        normalize_args({**base, "train_args": {"mesh": {"dp": 0}}})
    # booleans and ladder strings are all legal remat spellings
    for v in ("auto", True, False, "none", "attn", "block"):
        normalize_args({**base, "train_args": {"remat": v}})
    # ring + a forced remat rung is a rejected composition (checkpoint
    # around the shard_map ring loop fails its scan-carry typing)
    with pytest.raises(ValueError, match="ring"):
        normalize_args(
            {**base, "train_args": {
                "seq_attention": "ring", "remat": "block",
                "forward_steps": 16, "mesh": {"dp": 2, "sp": 4},
            }}
        )
