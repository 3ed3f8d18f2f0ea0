"""``ops/grouped_product.py`` with a ``period``: the kernels read a period of
a stack of weights where it lies and write that period of the stacked
gradient they are handed, every other period's bytes left as they were
(the Pallas interpreter); and ``ops/routed_experts.py`` ``held_mix`` under a
``lax.scan`` over periods, whose stacked gradient comes back through the
sinks in the scan's carry, against the same periods unrolled.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.ops import grouped_product
from handyrl_tpu.ops.grouped_product import _weight_sums, grouped_dot
from handyrl_tpu.ops.routed_experts import block_rows, held_mix, open_sinks, row_buffer

PERIODS, GROUPS, ROWS = 3, 4, 16
OWNER = (0, 0, 2, 2, 2, 3)      # group 1 holds no block
SENTINEL = 7.0

# (k, n, what ``_weight_sums``' tiles may take, its column tiles): a plain
# input matrix and a gated one (its [a, b] fused: twice the width), each as
# one tile and, under a scope cut down to the shape, as two column tiles
CASES = {
    "plain_whole": (64, 192, None, 1),
    "gated_whole": (64, 384, None, 1),
    "plain_column_tiles": (48, 512, 10 * 48 * 256, 2),
    "gated_column_tiles": (48, 1024, 10 * 48 * 512, 2),
}


def _operands(k, n, seed=0):
    key = jax.random.PRNGKey(seed + k + n)
    x = jax.random.normal(key, (ROWS * len(OWNER), k), jnp.bfloat16)
    stack = jax.random.normal(jax.random.fold_in(key, 1), (PERIODS, GROUPS, k, n), jnp.bfloat16) / 4
    dy = jax.random.normal(jax.random.fold_in(key, 2), (ROWS * len(OWNER), n), jnp.float32)
    return x, stack, jnp.asarray(OWNER, jnp.int32), dy


def _grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr, nested calls' too."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_period_of_the_stack_is_read_where_it_lies(case):
    """``grouped_dot(x, stack, owner, period=t)`` is ``grouped_dot(x,
    stack[t], owner)`` bit for bit at every period, the period traced."""
    k, n, _, _ = CASES[case]
    x, stack, owner, _ = _operands(k, n)
    at = jax.jit(lambda x, stack, t: grouped_dot(x, stack, owner, True, None, t))
    for t in range(PERIODS):
        want = grouped_dot(x, stack[t], owner, True)
        got = at(x, stack, jnp.int32(t))
        assert got.dtype == want.dtype == jnp.float32 and bool((got == want).all()), t


@pytest.mark.parametrize("first", [True, False], ids=["first_pass", "later_pass"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_gradient_lands_in_its_period_of_the_carried_stack(monkeypatch, case, first):
    """The weights' cotangent of a call with a period is the stacked sum it
    was handed with that period written: on a period's first pass the
    period-less gradient bit for bit, whatever the buffer held there; on a
    later pass what the buffer held plus the sums, added in float32 before
    the one cast.  Every other period's bytes are what the buffer held, and
    the rows' cotangent is the period-less call's."""
    k, n, scope, tiles = CASES[case]
    if scope is not None:
        monkeypatch.setattr(grouped_product, "_SUMS_BYTES", scope)
    x, stack, owner, dy = _operands(k, n)
    t = 1
    held = (jnp.full(stack.shape, SENTINEL, jnp.bfloat16) if first else
            8 * jax.random.normal(jax.random.PRNGKey(9), stack.shape, jnp.bfloat16))

    def pull(x, stack, held, t):
        into = (held, jnp.bool_(first))
        return jax.vjp(lambda x, w: grouped_dot(x, w, owner, True, into, t), x, stack)[1](dy)

    grids = [g for g in _grids(pull, x, stack, held, jnp.int32(t)) if len(g) == 3]
    assert grids == [(1, tiles, len(OWNER) + GROUPS)], grids
    d_x, d_stack = jax.jit(pull)(x, stack, held, jnp.int32(t))
    want_x, want_w = jax.vjp(lambda x, w: grouped_dot(x, w, owner, True), x, stack[t])[1](dy)
    assert d_x.dtype == jnp.bfloat16 and bool((d_x == want_x).all())
    assert d_stack.shape == stack.shape and d_stack.dtype == jnp.bfloat16
    for other in (0, 2):
        assert bool((d_stack[other] == held[other]).all()), other
    if first:
        assert bool((d_stack[t] == want_w).all())
        assert not np.asarray(want_w[1], np.float32).any()      # the group with no block: zeros
    else:
        exact = _weight_sums(x, dy.astype(jnp.bfloat16), owner, GROUPS, jnp.float32, True)
        summed = (held[t].astype(jnp.float32) + exact).astype(jnp.bfloat16)
        assert bool((d_stack[t] == summed).all())
        assert bool((d_stack[t, 1] == held[t, 1]).all())        # no block: what was there
        assert not bool((d_stack[t] == (held[t] + want_w)).all())   # not two roundings


def test_a_period_with_no_carried_stack_writes_into_zeros():
    """Differentiated with a period and no ``into``, the stack's gradient is
    the period's in a stack of zeros."""
    x, stack, owner, dy = _operands(64, 192)
    grad = jax.jit(jax.grad(
        lambda w, t: jnp.sum(grouped_dot(x, w, owner, True, None, t) * dy)))(stack, jnp.int32(2))
    want = jax.grad(lambda w: jnp.sum(grouped_dot(x, w, owner, True) * dy))(stack[2])
    assert bool((grad[2] == want).all())
    assert not np.asarray(grad[:2], np.float32).any()


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("passes", [1, 2], ids=["one_pass", "two_passes"])
def test_a_scan_over_periods_gives_the_unrolled_periods_gradients(gated, passes):
    """``held_mix`` under a ``lax.scan`` over three periods, handed the
    stacks, the period and the sinks, against the three periods one after
    another on ``stack[t]``: the same output, and the same gradient of the
    tokens, the gates and both stacks, bit for bit, with rows that fit the
    buffer and with rows that take a second pass over it; the scan's own
    reads of the stacks carry no gradient (``stop_gradient``), all of it
    comes back through the sinks."""
    tokens, d, width, held, experts, k = 320, 32, 64, 4, 32, 2
    rng = np.random.RandomState(passes)
    h = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    w1 = jnp.asarray(rng.randn(PERIODS, held, d, (2 if gated else 1) * width) / 4, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(PERIODS, held, width, d) / 4, jnp.bfloat16)
    valid = jnp.ones((tokens,), bool)
    if passes == 1:     # two choices over all experts: an eighth fall on the held four
        chosen = jnp.asarray(np.stack([rng.permutation(experts)[:k] for _ in range(tokens)]),
                             jnp.int32)
    else:               # every choice on a held expert: the buffer's worst case
        chosen = jnp.asarray(np.stack([rng.permutation(held)[:k] for _ in range(tokens)]),
                             jnp.int32)
    block = block_rows(tokens, k, experts, jnp.bfloat16)
    assert (block,) + row_buffer(tokens, k, held, experts, block) == (128, 6, 2)

    def unrolled(h, gates, w1, w2):
        took = []
        for t in range(PERIODS):
            out, counts = held_mix(h, chosen, gates, valid, w1[t], w2[t], 0, experts, gated)
            h = h + out
            took.append(counts["passes"])
        return h, jnp.stack(took)

    def scanned(h, gates, w1, w2):
        read = jax.lax.stop_gradient((w1, w2))

        def one_period(carry, t):
            h, sinks = carry
            out, counts = held_mix(h, chosen, gates, valid, *read, 0, experts, gated, t, sinks)
            return (h + out, counts["sinks"]), (counts["passes"], counts["in_place"])

        (h, sinks), (took, in_place) = jax.lax.scan(
            one_period, (h, (w1, w2)), jnp.arange(PERIODS))
        assert in_place.shape == (PERIODS,)
        return open_sinks(h, sinks), took

    weigh = jnp.asarray(rng.randn(tokens, d), jnp.float32)

    def both(fn):
        def loss(*operands):
            out, took = fn(*operands)
            return jnp.sum(out.astype(jnp.float32) * weigh), (out, took)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(h, gates, w1, w2)

    got, (out, took) = both(scanned)
    want, (want_out, want_took) = both(unrolled)
    assert bool((took == passes).all()) and bool((want_took == passes).all())
    assert bool((out == want_out).all())
    for name, a, b in zip(("h", "gates", "w1", "w2"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert bool((a == b).all()), name
