"""The routed experts of ``HybridNet`` (``ops/routed_experts.py`` ``held_mix``,
``choose``, ``row_buffer``, ``block_rows``; ``ops/grouped_product.py``'s
kernels in the Pallas interpreter; ``models/hybrid.py`` ``ExpertLayer``)
against plain loops over experts and over blocks, forward and gradients, at
the smallest shapes that still cross a block of rows, a tile of columns and a
pass of the buffer.  The published widths are lowered once, for a described
v5e, in tests/test_chip_compile.py (``-k "routed_experts or gated_top1"``).
Beside tests/test_grouped_product_live.py and test_grouped_product_periods.py;
cut from tests/test_hybrid_net.py (PR 67), whose net it shares.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.models.hybrid import ExpertLayer
from handyrl_tpu.ops import grouped_product, routed_experts
from handyrl_tpu.ops.grouped_product import BLOCK, FEW_ROWS, TILE, _weight_sums, grouped_dot
from handyrl_tpu.ops.routed_experts import (
    EXPERTS_SCOPE, SHARES, _owners, block_rows, choose, held_mix, row_buffer)
from nets import HYBRID, _module, _params, _random_window

REFERENCE = HYBRID.REFERENCE


# -- the expert layer's share -----------------------------------------------


def _expert_layer(held, offset, experts=32, top_k=6):
    return ExpertLayer(d_model=16, n_experts=experts, top_k=top_k, expert_width=8,
                       shared_width=24, routed_scale=2.5, experts_held=held, expert_offset=offset)


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Each of sixteen chips holds 2 of 32 experts, routes over all 32 and
    adds its own experts' terms: the shares' routed parts, with the shared
    expert counted once, are the uncut reference's whole layer."""
    h = jnp.asarray(np.random.RandomState(3).randn(5, 7, 16), jnp.float32)
    whole = _expert_layer(32, 0)
    params = whole.init(jax.random.PRNGKey(0), h)["params"]
    net = dict(top_k=6, routed_scale=2.5, experts_held=32, expert_offset=0)
    want, chosen = REFERENCE.experts(params, h, net)
    shared = jnp.square(jax.nn.relu(h @ params["shared_up"]["kernel"])) @ params["shared_down"]["kernel"]
    total = shared
    for share in range(16):
        held = dict(params, w1=params["w1"][2 * share:2 * share + 2],
                    w2=params["w2"][2 * share:2 * share + 2])
        out, picked, counts, _ = _expert_layer(2, 2 * share).apply({"params": held}, h)
        assert np.array_equal(np.sort(picked, -1), np.sort(chosen, -1))     # routes over all
        assert int(counts["rows"].sum()) == int(((chosen >= 2 * share) & (chosen < 2 * share + 2)).sum())
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_score_bias_changes_choices_and_not_gates():
    scores = jax.nn.sigmoid(jnp.asarray(np.random.RandomState(4).randn(50, 16), jnp.float32))
    bias = jnp.zeros(16).at[3].set(5.0)
    plain, _ = choose(scores, jnp.zeros(16), 4, 2.5)
    chosen, gates = choose(scores, bias, 4, 2.5)
    assert bool((chosen == 3).any(axis=-1).all()) and not np.array_equal(plain, chosen)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)      # the bias is not in the gates
    np.testing.assert_allclose(gates, 2.5 * picked / picked.sum(axis=-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(axis=-1), 2.5, rtol=1e-6)


@pytest.mark.parametrize("tokens", [40, 600, 3000])
def test_one_expert_given_every_token_drops_none(tokens):
    """Every token chooses the same two held experts: the rows outgrow the
    buffer (a uniform router's share and a block of padding an expert) and
    further passes take them."""
    rng = np.random.RandomState(5)
    d, width, held, experts, k = 16, 8, 4, 32, 2
    h = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    w1 = jnp.asarray(rng.randn(held, d, width) / 4, jnp.float32)
    w2 = jnp.asarray(rng.randn(held, width, d) / 3, jnp.float32)
    chosen = jnp.tile(jnp.asarray([[9, 10]], jnp.int32), (tokens, 1))
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.asarray(rng.rand(tokens) > 0.1)
    blocks, passes = row_buffer(tokens, k, held, experts, BLOCK)
    assert (passes > 1 and blocks * BLOCK < int(valid.sum()) * k) == (tokens > 500)
    out, counts = jax.jit(lambda *a: held_mix(*a, 8, experts))(h, chosen, gates, valid, w1, w2)
    assert counts["rows"].tolist() == [0, int(valid.sum()), int(valid.sum()), 0]
    act = lambda e: jnp.square(jax.nn.relu(h @ w1[e])) @ w2[e]  # noqa: E731
    want = valid[:, None] * (gates[:, :1] * act(1) + gates[:, 1:] * act(2))
    np.testing.assert_allclose(out, want, atol=2e-5)
    # and its gradient
    grad = jax.jit(jax.grad(
        lambda w: jnp.sum(held_mix(h, chosen, gates, valid, w, w2, 8, experts)[0] ** 2)))(w1)
    want = jax.jit(jax.grad(lambda w: jnp.sum((valid[:, None] * (
        gates[:, :1] * (jnp.square(jax.nn.relu(h @ w[1])) @ w2[1])
        + gates[:, 1:] * (jnp.square(jax.nn.relu(h @ w[2])) @ w2[2]))) ** 2)))(w1)
    np.testing.assert_allclose(grad, want, atol=2e-4 * float(jnp.abs(want).max()))


def _by_expert(h, chosen, gates, valid, w1, w2, offset):
    """The plain loop: for every held expert, every token that chose it,
    with the kernel's roundings (float32 accumulation, relu^2 in float32,
    the operands' dtype between the products and into the sum)."""
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(w1.shape[0]):
        up = jnp.dot(h, w1[e], preferred_element_type=jnp.float32)
        act = jnp.square(jax.nn.relu(up)).astype(h.dtype)
        down = jnp.dot(act, w2[e], preferred_element_type=jnp.float32)
        gate = jnp.where((chosen == e + offset) & valid[:, None], gates, 0.0).sum(axis=1)
        out = out + (down * gate[:, None]).astype(h.dtype)
    return out.astype(h.dtype)


def _close(got, want, dtype, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # bfloat16 keeps 8 bits: sums of a few hundred rounded terms in two orders
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(want).max())),
                               err_msg=what)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n", [(64, 192), (192, 64), (64, TILE + 64)])
def test_grouped_dot_is_each_blocks_rows_by_its_experts_weights(dtype, k, n):
    """The kernel (in the interpreter) against a loop over the blocks, forward
    and both gradients, at widths under a tile, not a multiple of 128, and
    over a tile of ``TILE`` columns with a partial last one (the published
    1,856 stood here and is under a tile: test_chip_compile.py lowers it); an
    expert with no block gets a zero gradient."""
    rng = np.random.RandomState(11)
    owner = jnp.asarray([0, 2, 2, 2, 4, 4], jnp.int32)       # 1 and 3 hold no block
    x = jnp.asarray(rng.randn(owner.size * BLOCK, k), dtype)
    w = jnp.asarray(rng.randn(5, k, n) / np.sqrt(k), dtype)

    def loop(x, w):
        blocks = x.reshape(owner.size, BLOCK, k)
        return jnp.concatenate([
            jnp.dot(blocks[b], w[int(e)], preferred_element_type=jnp.float32)
            for b, e in enumerate(np.asarray(owner))])

    _close(grouped_dot(x, w, owner, True), loop(x, w), dtype, "forward")
    weigh = jnp.asarray(rng.randn(x.shape[0], n), jnp.float32)
    grads = lambda fn: jax.jit(jax.grad(  # noqa: E731
        lambda x, w: jnp.sum(fn(x, w) * weigh), argnums=(0, 1)))(x, w)
    (dx, dw), (want_dx, want_dw) = grads(lambda x, w: grouped_dot(x, w, owner, True)), grads(loop)
    assert dx.dtype == dtype and dw.dtype == dtype
    _close(dx, want_dx, dtype, "rows' cotangent")
    _close(dw, want_dw, dtype, "weights' gradient")
    assert not np.asarray(dw[1], np.float32).any() and not np.asarray(dw[3], np.float32).any()


@pytest.mark.parametrize("k,n,tiles", [(64, 192, 1), (64, 512, 2)],
                         ids=["whole_tile", "column_tiles"])
def test_weight_sums_add_to_the_sum_a_loop_carries(monkeypatch, k, n, tiles):
    """``_weight_sums`` with ``into`` (the interpreter), at a shape that is
    one tile and at one that goes in two column tiles, as ``zaya1_8b``'s
    fused (2048, 4096) does under the kernel's scope (here under a scope cut
    down to the shape; the published one is test_chip_compile.py's to lower):
    past the first pass a group with blocks gets ``into`` plus its plain sum,
    added in float32 and rounded once, a group with none keeps ``into``; on
    the first pass the result is the plain sum whatever ``into`` holds (NaNs
    here), bit for bit what the kernel gives without ``into``."""
    # 10 bytes an element with the carried sum's tile: ``_weight_sums``' rule
    assert 10 * 2048 * 4096 > grouped_product._SUMS_BYTES >= 10 * 2048 * 2048
    if tiles > 1:
        monkeypatch.setattr(grouped_product, "_SUMS_BYTES", 10 * k * n // tiles)
    key = jax.random.PRNGKey(k)
    x = jax.random.normal(key, (3 * 16, k), jnp.bfloat16)
    dy = jax.random.normal(jax.random.fold_in(key, 1), (3 * 16, n), jnp.bfloat16)
    owner = jnp.array([0, 2, 2], jnp.int32)        # 1 and 3 hold no block
    into = 8 * jax.random.normal(jax.random.fold_in(key, 2), (4, k, n), jnp.bfloat16)
    plain = _weight_sums(x, dy, owner, 4, jnp.bfloat16, True)
    exact = _weight_sums(x, dy, owner, 4, jnp.float32, True)
    assert np.asarray(exact[0]).any() and np.asarray(exact[2]).any()
    assert not np.asarray(exact[1]).any() and not np.asarray(exact[3]).any()

    later = _weight_sums(x, dy, owner, 4, jnp.bfloat16, True, into, jnp.bool_(False))
    want = (into.astype(jnp.float32) + exact).astype(jnp.bfloat16)
    assert later.dtype == jnp.bfloat16 and bool((later == want).all())
    assert bool((later[1] == into[1]).all()) and bool((later[3] == into[3]).all())
    assert not bool((later[0] == plain[0]).all())

    first = _weight_sums(x, dy, owner, 4, jnp.bfloat16, True,
                         jnp.full_like(into, jnp.nan), jnp.bool_(True))
    assert bool((first == plain).all())


def _routing(rng, tokens, rows_of, held, offset, k):
    """chosen (tokens, k): expert ``offset + e`` is chosen by exactly
    ``rows_of[e]`` tokens, no token choosing an expert twice; every other
    choice falls on an expert that is not held."""
    picks = np.concatenate([np.full(r, offset + e) for e, r in enumerate(rows_of)])
    assert picks.size <= tokens * k
    picks = np.concatenate([picks, np.full(tokens * k - picks.size, -1)]).reshape(k, tokens).T
    picks = np.where(picks < 0, offset + held + np.arange(k)[None, :], picks)
    assert (np.diff(np.sort(picks, axis=1), axis=1) > 0).all()
    return jnp.asarray(picks[rng.permutation(tokens)], jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("rows_of,passes,experts", [
    ((0, 128, 40, 300), 1, 32),         # an expert with no row, one with exactly a block
    ((500, 0, 257, 129), 2, 32),        # the rows outgrow the buffer once
    ((513, 1, 385, 381), 3, 64),        # and twice: 13 blocks of rows in a buffer of 6
], ids=["one_pass", "two_passes", "three_passes"])
def test_held_mix_is_the_loop_over_experts_and_so_are_its_gradients(monkeypatch, dtype, rows_of,
                                                                    passes, experts):
    """bfloat16 operands go through the grouped kernel (the interpreter
    here), float32 ones through the plain block products: both are the loop
    over experts, forward and for the gradients of ``h``, ``gates``, ``w1``
    and ``w2``, at an expert width that is no multiple of 128.  The kernel
    sums the weights' gradients into the backward loop's carry: with one
    pass they are bit for bit what one pass outside any loop gives, its
    kernels called once without a carried sum."""
    rng = np.random.RandomState(7)
    tokens, d, width, held, k, offset = 640, 32, 192, 4, 2, 8
    assert block_rows(tokens, k, experts, dtype) == BLOCK   # 20 or 40 rows an expert: the MXU's tile
    blocks, _ = row_buffer(tokens, k, held, experts, BLOCK)
    assert blocks == math.ceil(SHARES * tokens * k * held / (experts * BLOCK)) + held
    assert blocks == {32: 8, 64: 6}[experts]
    h = jnp.asarray(rng.randn(tokens, d), dtype)
    w1 = jnp.asarray(rng.randn(held, d, width) / 4, dtype)
    w2 = jnp.asarray(rng.randn(held, width, d) / 8, dtype)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.ones(tokens, bool)
    chosen = _routing(rng, tokens, rows_of, held, offset, k)
    weigh = jnp.asarray(rng.randn(tokens, d), jnp.float32)

    out, counts = jax.jit(lambda *a: held_mix(*a, offset, experts))(h, chosen, gates, valid, w1, w2)
    assert counts["rows"].tolist() == list(rows_of)
    assert int(counts["passes"]) == passes and int(counts["slots"]) == passes * blocks * BLOCK
    _close(out, _by_expert(h, chosen, gates, valid, w1, w2, offset), dtype, "forward")

    def grads(fn):
        return jax.jit(jax.grad(
            lambda h, g, a, b: jnp.sum(fn(h, g, a, b).astype(jnp.float32) * weigh),
            argnums=(0, 1, 2, 3)))(h, gates, w1, w2)

    got = grads(lambda h, g, a, b: held_mix(h, chosen, g, valid, a, b, offset, experts)[0])
    want = grads(lambda h, g, a, b: _by_expert(h, chosen, g, valid, a, b, offset))
    for name, a, b in zip(("h", "gates", "w1", "w2"), got, want):
        assert a.dtype == b.dtype
        _close(a, b, dtype, "gradient of " + name)
    if 0 in rows_of:     # the expert with no row: its weights get no gradient
        empty = rows_of.index(0)
        assert not np.asarray(got[2][empty], np.float32).any()
        assert not np.asarray(got[3][empty], np.float32).any()
    if dtype == jnp.bfloat16 and passes == 1:
        monkeypatch.setattr(
            routed_experts, "_passes", lambda h, gates, w1, w2, route, blocks, block, gated:
            routed_experts._one_pass(h, gates, w1, w2, route, 0, blocks, block, gated))
        once = grads(lambda h, g, a, b: held_mix(h, chosen, g, valid, a, b, offset, experts)[0])
        assert bool((got[2] == once[2]).all()) and bool((got[3] == once[3]).all())


def test_the_work_is_the_buffers_whatever_the_routing():
    """Two routings of one shape lower to the same program, every block of
    the buffer has an expert in both (the experts' blocks are consecutive and
    add up to the buffer), and the slots computed are the same while the rows
    differ."""
    rng = np.random.RandomState(9)
    tokens, d, width, held, experts, k, offset = 640, 16, 64, 4, 32, 2, 8
    h = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(held, d, width) / 4, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(held, width, d) / 8, jnp.bfloat16)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.ones(tokens, bool)
    mix = jax.jit(lambda *a: held_mix(*a, offset, experts))
    blocks, texts, counted = row_buffer(tokens, k, held, experts, BLOCK)[0], [], []
    for rows_of in ((100, 100, 100, 100), (0, 3, 500, 129)):
        chosen = _routing(rng, tokens, rows_of, held, offset, k)
        texts.append(mix.lower(h, chosen, gates, valid, w1, w2).as_text())
        counted.append(jax.device_get(mix(h, chosen, gates, valid, w1, w2)[1]))
        padded = -(-np.asarray(rows_of) // BLOCK) * BLOCK
        owner = np.asarray(_owners(jnp.cumsum(jnp.asarray(padded)), 0, blocks, BLOCK))
        sizes = np.bincount(owner, minlength=held)
        assert sizes.sum() == blocks and (np.diff(owner) >= 0).all()
        # each expert has its padded rows' blocks, the last one the unfilled ones too
        assert (sizes[:-1] * BLOCK == padded[:-1]).all() and sizes[-1] * BLOCK >= padded[-1]
    assert texts[0] == texts[1]
    assert counted[0]["slots"] == counted[1]["slots"] == blocks * BLOCK
    assert counted[0]["passes"] == counted[1]["passes"] == 1
    assert counted[0]["rows"].sum() == 400 and counted[1]["rows"].sum() == 632


# the acting cell's shape cut down (granite_actor_b32: 32 rows a step, top-10 of 72, 36 held)
_FEW = dict(tokens=32, d=64, width=24, held=36, experts=72, k=10)


def _few_rows_routing(case):
    """chosen (32, 10) over 72 experts of which the first 36 are held."""
    rng, tokens, held, experts, k = np.random.RandomState(13), *(
        _FEW[key] for key in ("tokens", "held", "experts", "k"))
    if case == "cell":              # a router of the cell's kind: any ten of 72 a token
        picks = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif case == "one_expert":      # every pair on one held expert
        picks = np.full((tokens, k), 7)
    elif case == "all_held":        # every pair on held experts: the worst case
        picks = np.stack([rng.permutation(held)[:k] for _ in range(tokens)])
    else:                           # "none_held"
        picks = np.stack([held + rng.permutation(experts - held)[:k] for _ in range(tokens)])
    return jnp.asarray(picks, jnp.int32)


FEW_ROWS_CASES = ["cell", "one_expert", "all_held", "none_held"]


@pytest.mark.parametrize("tokens,k,experts,held,dtype,block,blocks", [
    (32, 10, 72, 36, jnp.bfloat16, 16, 56),      # granite_actor_b32's window: 896 slots, not 4,992
    (64, 10, 72, 36, jnp.bfloat16, 16, 76),      # its replay
    (32, 10, 72, 36, jnp.float32, 128, 39),      # float32 products copy a block's weights out
    (128, 10, 72, 36, jnp.bfloat16, 128, 46),    # 17.8 rows an expert: the MXU's tile
    (6144, 6, 128, 8, jnp.bfloat16, 128, 53),    # nemotron_twotower_train_t192's two parts
    (512, 6, 128, 8, jnp.bfloat16, 128, 12),
    (2, 6, 128, 8, jnp.bfloat16, 16, 9),
])
def test_a_blocks_height_follows_the_rows_an_expert_gets(tokens, k, experts, held, dtype, block,
                                                         blocks):
    """``block_rows`` reads shapes and dtype alone: 16 rows where a uniform
    router gives an expert fewer and the products are the kernel's, else 128;
    ``row_buffer`` counts its blocks in that height, and one pass of it
    covers every pair on held experts where the blocks are low."""
    assert (FEW_ROWS, BLOCK) == (16, 128)
    assert block_rows(tokens, k, experts, dtype) == block
    got, passes = row_buffer(tokens, k, held, experts, block)
    assert got == blocks
    assert passes * got * block >= tokens * min(k, held) and (passes == 1 or block == BLOCK)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", FEW_ROWS_CASES)
def test_held_mix_in_blocks_of_sixteen_is_held_mix_in_blocks_of_128(monkeypatch, case, dtype):
    """The layout moves no number: a row's products, its ``silu(a) b`` and a
    token's sum choice by choice are the same whichever slot the row lies
    in, forward and for every gradient: bit for bit in bfloat16 (the kernel
    in the interpreter), to float32's last digits through the block products
    (the CPU's ``dot`` sums a row in another order at another height); and
    ``counts["slots"]`` is the passes x blocks x rows of a block."""
    rng = np.random.RandomState(17)
    tokens, d, width, held, experts, k = (
        _FEW[key] for key in ("tokens", "d", "width", "held", "experts", "k"))
    h = jnp.asarray(rng.randn(tokens, d), dtype)
    w1 = jnp.asarray(rng.randn(held, d, 2 * width) / 8, dtype)
    w2 = jnp.asarray(rng.randn(held, width, d) / 5, dtype)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.asarray(np.arange(tokens) != 5)
    chosen = _few_rows_routing(case)
    weigh = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    live = int(((np.asarray(chosen) < held) & np.asarray(valid)[:, None]).sum())

    def both(block):
        monkeypatch.setattr(routed_experts, "block_rows", lambda *a: block)
        mix = lambda h, g, a, b: held_mix(h, chosen, g, valid, a, b, 0, experts, True)  # noqa: E731
        out, counts = jax.jit(mix)(h, gates, w1, w2)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(mix(*a)[0].astype(jnp.float32) * weigh), argnums=(0, 1, 2, 3)))(
                h, gates, w1, w2)
        blocks, passes = row_buffer(tokens, k, held, experts, block)
        assert int(counts["rows"].sum()) == live
        assert int(counts["slots"]) == int(counts["passes"]) * blocks * block
        # the kernels in blocks of 128 run the blocks that hold a row; in blocks of 16, and the
        # plain products, every block
        skips = dtype == jnp.bfloat16 and block == BLOCK
        assert int(counts["blocks_run"]) == (
            int((-(-np.asarray(counts["rows"]) // block) * block).sum()) if skips
            else int(counts["slots"]))
        return out, grads, int(counts["passes"]), passes

    low, low_grads, low_passes, covers = both(FEW_ROWS)
    tall, tall_grads, _, _ = both(BLOCK)
    assert covers == 1 and low_passes == 1     # 56 blocks of 16 hold 320 rows on any 36 experts
    assert low.dtype == dtype and (np.asarray(low, np.float32).any() == (case != "none_held"))
    for name, a, b in zip(("out", "h", "gates", "w1", "w2"), (low, *low_grads), (tall, *tall_grads)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        tol = 0.0 if dtype == jnp.bfloat16 else 2e-6 * max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(a, b, rtol=0, atol=tol, err_msg=name)


@pytest.mark.parametrize("case", FEW_ROWS_CASES)
def test_a_block_of_sixteen_belongs_to_one_expert(case):
    """``_owners`` at 16 rows a block: non-decreasing, every block has an
    owner, and each held expert's rows lie in blocks that are his alone."""
    tokens, held, experts, k = (_FEW[key] for key in ("tokens", "held", "experts", "k"))
    chosen = np.asarray(_few_rows_routing(case))
    rows = np.bincount(chosen[chosen < held], minlength=held)
    padded = -(-rows // FEW_ROWS) * FEW_ROWS
    blocks = row_buffer(tokens, k, held, experts, FEW_ROWS)[0]
    assert padded.sum() <= blocks * FEW_ROWS       # one pass
    owner = np.asarray(_owners(jnp.cumsum(jnp.asarray(padded)), 0, blocks, FEW_ROWS))
    assert owner.shape == (blocks,) and (np.diff(owner) >= 0).all()
    assert owner.min() >= 0 and owner.max() < held
    base = np.cumsum(padded) - padded
    for e in np.flatnonzero(rows):
        mine = np.arange(base[e] // FEW_ROWS, (base[e] + padded[e]) // FEW_ROWS)
        assert (owner[mine] == e).all()
        assert (np.flatnonzero(owner == e)[:mine.size] == mine).all()   # and no block before them
    # an expert with no row has no block, but the last, who owns what no row fills
    assert not np.isin(owner, np.flatnonzero(rows[:-1] == 0)).any()


def test_the_net_counts_its_buffers_slots_and_the_passes_past_the_first():
    """``counter_buffer_slots`` and ``counter_expert_passes`` of a window:
    a router near uniform fills one pass of every buffer (0 passes past the
    first), one whose bias sends every token to two held experts outgrows
    the forward part's buffer once."""
    module = _module(HYBRID, pattern="E", n_experts=32, top_k=2, experts_held=4, expert_offset=8)
    obs, _ = _random_window(2, rows=6, steps=108, observed=1.1)
    params = _params(module, obs)
    seen = jnp.ones((6, 108), jnp.float32)

    def counters(bias):
        mixer = dict(params["layer0"]["mixer"], score_bias=jnp.asarray(bias, jnp.float32))
        p = dict(params, layer0=dict(params["layer0"], mixer=mixer))
        return jax.device_get(module.apply(
            {"params": p}, obs, None, seq=True, key_mask=seen, burn_in=8)["counters"])

    sizes = [row_buffer(n, 2, 4, 32, BLOCK)[0] * BLOCK for n in (6 * 8, 6 * 100)]   # float32: 128
    plain = counters(np.zeros(32))
    assert plain["buffer_slots"] == sum(sizes) and plain["expert_passes"] == 0
    assert plain["slots_run"] == plain["buffer_slots"]      # float32: the plain products skip none
    assert 0 < plain["rows_held"] < 0.5 * 2 * 6 * 108
    skewed = counters(np.eye(32)[[9, 10]].sum(axis=0) * 10.0)
    assert skewed["rows_held"] == 2 * 6 * 108          # every choice of every token
    # the forward part's 1,200 rows in two experts' 640 slots each, a 896-slot buffer: a second pass
    assert skewed["expert_passes"] == 1 and skewed["buffer_slots"] == sizes[0] + 2 * sizes[1]


def test_every_product_of_the_gradient_sits_under_the_experts_scope():
    """Forward and backward: each product of the grouped kernel in the
    compiled gradient (on the CPU the interpreter's ``dot``s, the only ones
    ``held_mix`` has) carries ``experts`` as a component of its ``op_name``,
    as ``benchmark.trace_reduce.scopes_of`` reads a profile: the custom
    VJP's backward products inherit the scope their forward call was made
    under (else ``experts_roofline`` would time the forward products alone).
    tests/test_chip_compile.py reads the same off the kernel's calls in the
    program compiled for a v5e."""
    import re

    from benchmark import trace_reduce

    rng = np.random.RandomState(3)
    tokens, d, width, held, experts, k, offset = 64, 16, 32, 4, 32, 2, 8
    h = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(held, d, width), jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(held, width, d), jnp.bfloat16)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    chosen = _routing(rng, tokens, (20, 0, 30, 5), held, offset, k)
    loss = lambda h, g, a, b: jnp.sum(  # noqa: E731
        held_mix(h, chosen, g, jnp.ones(tokens, bool), a, b, offset, experts)[0].astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(h, gates, w1, w2).compile().as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if re.search(r"= \S+ dot\(", line)]
    # two forward products, their two rows' cotangents and two weight sums
    assert len(names) == 6
    outside = [n for n in names if trace_reduce.scopes_of(n, [EXPERTS_SCOPE]) != [EXPERTS_SCOPE]]
    assert not outside, outside

