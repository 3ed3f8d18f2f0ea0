"""Parallelism tests on the 8-device virtual CPU mesh: ring attention
(sequence parallelism) golden-checked against full attention, and
tensor-parallel ('mp') parameter sharding through a real train step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.ops import full_attention_reference, ring_self_attention
from handyrl_tpu.parallel import make_mesh, param_shardings
from nets import _env_batch

# The ring paths' varying-type marking is a compat ladder (pcast -> pvary
# -> identity on pre-VMA jax like this container's 0.4.37, where shard_map
# has no varying types and marking is a no-op) — ops/ring_attention.py
# _ring_loop.  The former version-gated skips here are real passes on
# every branch of the ladder.


def _qkv(key, B=2, T=16, H=2, D=4):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("mesh_spec", [{"sp": 8}, {"dp": 2, "sp": 4}])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(mesh_spec, causal):
    mesh = make_mesh(mesh_spec)
    q, k, v = _qkv(jax.random.PRNGKey(0))
    out = ring_self_attention(q, k, v, mesh, causal=causal)
    ref = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_no_sp_axis_fallback():
    mesh = make_mesh({"dp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(1))
    out = ring_self_attention(q, k, v, mesh, causal=True)
    ref = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ring_attention_differentiable():
    # slow leg: the 8-shard grad compile is the expensive half of the ring
    # battery; the forward goldens above stay in tier-1, and the grad path
    # is also pinned end-to-end by test_transformer_train_step_ring_sp
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(2))

    def loss_ring(q, k, v):
        return (ring_self_attention(q, k, v, mesh, causal=True) ** 2).sum()

    def loss_full(q, k, v):
        return (full_attention_reference(q, k, v, causal=True) ** 2).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), rtol=1e-4, atol=1e-4)


def _masked_case(seed, B, T, H, D, observed_frac=0.7):
    q, k, v = _qkv(jax.random.PRNGKey(seed), B, T, H, D)
    km = jax.random.uniform(jax.random.PRNGKey(seed + 50), (B, T))
    key_mask = (km < observed_frac).astype(jnp.float32)
    slopes = 2.0 ** (-jnp.arange(1, H + 1, dtype=jnp.float32))
    return q, k, v, key_mask, slopes


@pytest.mark.parametrize("mesh_spec", [{"sp": 8}, {"dp": 2, "sp": 4}])
@pytest.mark.parametrize("window", [1 << 30, 6])
def test_masked_ring_attention_matches_reference(mesh_spec, window):
    """Sequence-parallel attention with the PRODUCTION transformer
    semantics (observation masks, observed-age ALiBi, window eviction) vs
    the exact einsum the einsum branch executes."""
    from handyrl_tpu.ops import masked_ring_self_attention
    from handyrl_tpu.ops.flash_attention import masked_attention_reference

    mesh = make_mesh(mesh_spec)
    q, k, v, key_mask, slopes = _masked_case(3, 2, 16, 2, 4)
    out = masked_ring_self_attention(q, k, v, key_mask, slopes, mesh, window=window)
    ref = masked_attention_reference(q, k, v, key_mask, slopes, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_masked_ring_attention_differentiable():
    from handyrl_tpu.ops import masked_ring_self_attention
    from handyrl_tpu.ops.flash_attention import masked_attention_reference

    mesh = make_mesh({"sp": 8})
    q, k, v, key_mask, slopes = _masked_case(4, 1, 16, 2, 4)

    def loss_ring(q, k, v):
        return (
            masked_ring_self_attention(q, k, v, key_mask, slopes, mesh, window=6) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            masked_attention_reference(q, k, v, key_mask, slopes, window=6) ** 2
        ).sum()

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf), rtol=1e-4, atol=1e-4)


def test_masked_ring_no_sp_axis_fallback():
    from handyrl_tpu.ops import masked_ring_self_attention
    from handyrl_tpu.ops.flash_attention import masked_attention_reference

    mesh = make_mesh({"dp": 8})
    q, k, v, key_mask, slopes = _masked_case(5, 2, 16, 2, 4)
    out = masked_ring_self_attention(q, k, v, key_mask, slopes, mesh, window=6)
    ref = masked_attention_reference(q, k, v, key_mask, slopes, window=6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_param_shardings_mp_axis():
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables

    mesh = make_mesh({"dp": 4, "mp": 2})
    env = make_env({"env": "TicTacToe"})
    module = env.net()
    params = init_variables(module, env)["params"]
    shardings = param_shardings(mesh, params)

    leaves = jax.tree.leaves(shardings)
    param_leaves = jax.tree.leaves(params)
    sharded = [
        s for s, p in zip(leaves, param_leaves)
        if np.asarray(p).ndim >= 2 and np.asarray(p).shape[-1] % 2 == 0
    ]
    assert sharded, "expected at least one mp-sharded kernel"
    assert all("mp" in (s.spec[-1] or ()) or s.spec[-1] == "mp" for s in sharded)


def test_train_step_with_mp_mesh():
    """Full sharded train step on a dp x mp mesh ends with finite loss."""
    from handyrl_tpu.parallel import TrainContext

    module, variables, batch, args = _env_batch({"env": "TicTacToe"}, {"mesh": {"dp": 4, "mp": 2}})
    mesh = make_mesh(args["mesh"])
    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(variables["params"])
    state, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
    total = float(jax.device_get(metrics["total"]))
    assert np.isfinite(total)
    # params kept their tensor-parallel layout through the donated update
    kernel_shardings = [
        x.sharding.spec for x in jax.tree.leaves(state["params"]) if x.ndim >= 2
    ]
    assert any("mp" in [a for a in spec if a] for spec in kernel_shardings)


@pytest.mark.parametrize(
    "env_args,overrides",
    [
        ({"env": "TicTacToe"}, {}),                                    # feed-forward
        ({"env": "Geister"}, {"observation": True}),                   # DRC scan
        (
            {"env": "TicTacToe", "net": "transformer"},
            {"observation": True, "burn_in_steps": 2},                 # seq attention
        ),
    ],
)
def test_train_step_bfloat16(env_args, overrides):
    """bf16 compute path: finite loss close to fp32, fp32 master weights."""
    from handyrl_tpu.parallel import TrainContext

    module, variables, batch, args = _env_batch(env_args, overrides)
    mesh = make_mesh({"dp": -1})

    losses = {}
    for dtype in ("float32", "bfloat16"):
        ctx = TrainContext(module, {**args, "compute_dtype": dtype}, mesh)
        state = ctx.init_state(variables["params"])
        state, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
        losses[dtype] = float(jax.device_get(metrics["total"]))
        assert np.isfinite(losses[dtype])
        assert all(
            x.dtype == np.float32
            for x in jax.tree.leaves(state["params"])
        ), "master weights must stay fp32"
    assert abs(losses["bfloat16"] - losses["float32"]) < 0.1 * (abs(losses["float32"]) + 1.0)
