"""``ops/grouped_product.py`` with ``live``: the kernels run the blocks before
it and no others, forward and in both backward kernels, and what they leave
for the other blocks' rows is uninitialised; ``ops/routed_experts.py``
``held_mix`` hands them the blocks that hold a row, and nothing it returns
moves when every row a kernel skipped is NaN (the Pallas interpreter).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.ops import grouped_product, routed_experts
from handyrl_tpu.ops.grouped_product import grouped_dot
from handyrl_tpu.ops.routed_experts import block_rows, held_mix, row_buffer

PERIODS, GROUPS, ROWS, K, N = 2, 4, 16, 32, 128
OWNER = (0, 0, 2, 2, 2, 3)      # group 1 holds no block
LIVE = {"none": 0, "one": 1, "some": 3, "all": len(OWNER)}


@functools.lru_cache(maxsize=None)
def _pull(stacked: bool, carried: bool):
    """(x, w, held, dy, live) -> (out, d_x, d_w) of ``grouped_dot`` with a
    traced ``live``, jitted once a (period, into) pair: with a period ``w``
    and ``held`` are stacks and the period is 1; ``held`` is the sum a loop
    carries, past its first pass."""
    owner = jnp.asarray(OWNER, jnp.int32)

    def pull(x, w, held, dy, live):
        period = jnp.int32(1) if stacked else None
        into = (held, jnp.bool_(False)) if carried else None
        out, back = jax.vjp(
            lambda x, w: grouped_dot(x, w, owner, True, into, period, live), x, w)
        return (out, *back(dy))

    return jax.jit(pull)


@pytest.mark.parametrize("carried", [False, True], ids=["fresh", "into"])
@pytest.mark.parametrize("stacked", [False, True], ids=["flat", "period"])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_kernels_run_the_live_blocks_and_no_others(live, stacked, carried):
    """``grouped_dot``'s output and its rows' cotangent on the live blocks,
    and the weights' cotangent, are the plain ``einsum`` products over the
    live blocks alone: a block past ``live`` adds nothing to its group's sum
    (a group left with none gets zeros, or what the carried sum held), with
    and without a period, with and without a carried sum."""
    live = LIVE[live]
    key = jax.random.PRNGKey(live)
    x = jax.random.normal(key, (ROWS * len(OWNER), K), jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(key, 1), (PERIODS, GROUPS, K, N), jnp.bfloat16) / 4
    held = jax.random.normal(jax.random.fold_in(key, 2), w.shape, jnp.bfloat16)
    dy = jax.random.normal(jax.random.fold_in(key, 3), (ROWS * len(OWNER), N), jnp.float32)
    if not stacked:
        w, held = w[1], held[1]
    out, d_x, d_w = _pull(stacked, carried)(x, w, held, dy, jnp.int32(live))
    assert (out.dtype, d_x.dtype, d_w.dtype) == (jnp.float32, jnp.bfloat16, jnp.bfloat16)
    assert d_w.shape == w.shape

    owner, rows = np.asarray(OWNER), live * ROWS
    blocks = lambda a: np.asarray(a, np.float32).reshape(len(OWNER), ROWS, -1)[:live]  # noqa: E731
    weights = np.asarray(w[1] if stacked else w, np.float32)[owner[:live]]
    cast = np.asarray(dy.astype(jnp.bfloat16), np.float32)    # the MXU's operand
    np.testing.assert_allclose(
        np.asarray(out)[:rows], np.einsum("brk,bkn->brn", blocks(x), weights).reshape(rows, N),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(d_x, np.float32)[:rows],
        np.einsum("brn,bkn->brk", blocks(cast), weights).reshape(rows, K), rtol=1e-2, atol=1e-2)
    sums = np.zeros((GROUPS, K, N), np.float32)
    np.add.at(sums, owner[:live], np.einsum("brk,brn->bkn", blocks(x), blocks(cast)))
    before = np.asarray(held[1] if stacked else held, np.float32) if carried else 0.0
    got = np.asarray(d_w[1] if stacked else d_w, np.float32)
    np.testing.assert_allclose(got, sums + before, rtol=1e-2, atol=1e-2)
    for group in set(range(GROUPS)) - set(owner[:live]):    # no live block: nothing added
        assert np.array_equal(got[group], before[group] if carried else np.zeros((K, N)))
    if stacked:     # the other period's bytes: what the sum held, or the zeros it started as
        assert np.array_equal(
            np.asarray(d_w[0], np.float32), np.asarray(held[0], np.float32) if carried else 0 * got)


def _poisoned(real):
    """``_rows_times`` whose rows past the live blocks are NaN, as memory
    that no grid step wrote may be."""
    def rows_times(x, w, owner, transposed, out_dtype, interpret, period=None, live=None):
        out = real(x, w, owner, transposed, out_dtype, interpret, period, live)
        written = jnp.arange(x.shape[0]) < live * (x.shape[0] // owner.size)
        return jnp.where(written[:, None], out, jnp.nan)

    return rows_times


@pytest.mark.parametrize("passes", [1, 2], ids=["one_pass", "two_passes"])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "gated"])
def test_nothing_sums_a_row_the_kernels_skipped(monkeypatch, gated, passes):
    """``held_mix`` and its gradients to the tokens, the gates and both
    weights with every skipped row of every kernel output NaN: finite, and
    bit for bit what comes of kernels that run every block (``live`` kept
    from them), with rows that fit the buffer and rows that take a second
    pass, of which the last blocks are empty.  ``blocks_run`` counts the
    slots of the blocks that hold a row."""
    tokens, d, width, held, experts, k = 320, 32, 64, 4, 32, 2
    rng = np.random.RandomState(passes)
    h = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    w1 = jnp.asarray(rng.randn(held, d, (2 if gated else 1) * width) / 4, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(held, width, d) / 4, jnp.bfloat16)
    valid = jnp.ones((tokens,), bool)
    among = experts if passes == 1 else held     # an eighth on the held four, or every choice
    chosen = jnp.asarray(np.stack([rng.permutation(among)[:k] for _ in range(tokens)]), jnp.int32)
    block = block_rows(tokens, k, experts, jnp.bfloat16)
    blocks = row_buffer(tokens, k, held, experts, block)[0]
    weigh = jnp.asarray(rng.randn(tokens, d), jnp.float32)

    def both():
        def loss(h, gates, w1, w2):
            out, counts = held_mix(h, chosen, gates, valid, w1, w2, 0, experts, gated)
            return jnp.sum(out.astype(jnp.float32) * weigh), (out, counts)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(h, gates, w1, w2)

    with monkeypatch.context() as every_block:
        every_block.setattr(
            routed_experts, "grouped_dot",
            lambda x, w, owner, interpret, into, period, live: grouped_dot(
                x, w, owner, interpret, into, period))
        want, (want_out, _) = both()
    monkeypatch.setattr(grouped_product, "_rows_times", _poisoned(grouped_product._rows_times))
    got, (out, counts) = both()

    rows = np.asarray(counts["rows"])
    assert int(counts["passes"]) == passes
    assert int(counts["blocks_run"]) == int((-(-rows // block) * block).sum())
    assert int(counts["blocks_run"]) < int(counts["slots"]) == passes * blocks * block
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all()) and bool((out == want_out).all())
    for name, a, b in zip(("h", "gates", "w1", "w2"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert bool((a == b).all()), name


def test_a_net_with_no_routed_layer_holds_nothing_of_this():
    """A dense trunk's window (``ouro_*``'s shape of net): no grouped kernel
    in its gradient's jaxpr and no routed counter among its counters, so
    its step lowers to what it lowered to before the kernels skipped."""
    from handyrl_tpu.models.hybrid import HybridNet

    module = HybridNet(num_actions=5, pattern="*-", loops=2, sandwich=True, d_model=32,
                       norm_eps=1e-6, n_heads=2, n_kv_heads=2, head_dim=16, rope_theta=1e6,
                       mlp_width=48, memory_len=50)
    rng = np.random.RandomState(0)
    obs = {"a": jnp.asarray(rng.randn(2, 6, 7), jnp.float32)}
    mask = jnp.ones((2, 6), jnp.float32)
    first = jax.tree.map(lambda x: x[:, 0], obs)
    params = module.init(jax.random.PRNGKey(0), first, None)["params"]

    def loss(params):
        out = module.apply({"params": params}, obs, None, seq=True, key_mask=mask)
        return out["policy"].astype(jnp.float32).sum(), out["counters"]

    text = str(jax.make_jaxpr(jax.grad(loss, has_aux=True))(params))
    assert "pallas_call" not in text and "_rows_times" not in text
    counters = jax.eval_shape(loss, params)[1]
    assert not {"slots_run", "buffer_slots", "rows_held"} & set(counters), sorted(counters)
