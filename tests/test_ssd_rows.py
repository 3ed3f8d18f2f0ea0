"""``ops/ssd.py`` ``ssd_step_rows``: one step of the selective-state scan on
one row a lane of a state kept per (lane, player), in place, against
``ssd_step``'s lines on the gathered rows.  Where ``rows_fit`` the Pallas
kernel (its interpreter here: what the TPU's compiler says of it is
``tests/test_chip_compile.py``'s), else the lines round a gather and scatters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.ops import ssd

# (lanes, players, heads, head_dim, groups, state_size), whether the kernel takes it
SHAPES = [
    ((3, 2, 128, 64, 1, 128), True),    # granite_4_0_h_small's mixer, as published
    ((5, 2, 16, 16, 2, 128), True),
    ((4, 3, 32, 8, 2, 128), True),      # three players: two rows a lane that do not act
    ((4, 2, 4, 16, 1, 16), False),      # the tiny nets' state: no 128 lanes
    ((4, 2, 4, 12, 1, 128), False),     # heads that are no whole tiles of 8 rows
    ((4, 2, 8, 16, 2, 128), False),     # a group's heads that are half a chunk of 128 rows
]


def _operands(shape, seed=0, dtype=jnp.bfloat16):
    n, players, h, p, g, s = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(keys[0], (n, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (n, h)))
    A = -jnp.exp(jax.random.normal(keys[2], (h,)))
    B = jax.random.normal(keys[3], (n, g, s), dtype)
    C = jax.random.normal(keys[4], (n, g, s), dtype)
    state = jax.random.normal(keys[5], (n, players, h, p, s))
    player = jax.random.randint(keys[6], (n,), 0, players)
    # lane 0 has just begun, lane 1 has not, the others as drawn
    fresh = jax.random.bernoulli(keys[7], 0.5, (n,)).at[0].set(True).at[1].set(False)
    return (x, dt, A, B, C, state), player, fresh


@pytest.mark.parametrize("shape,kernel", SHAPES)
def test_a_step_of_the_acting_rows_is_ssd_steps_and_no_other_row_moves(shape, kernel):
    one, player, fresh = _operands(shape)
    state = one[-1]
    n, players = state.shape[:2]
    tail = jax.random.normal(jax.random.PRNGKey(11), (n, players, 3, 256))
    rows_in = jax.random.normal(jax.random.PRNGKey(12), (n, 3, 256))
    y, new, leaf = ssd.ssd_step_rows(*one, player, fresh, (tail, rows_in))
    chosen = ssd.ROW_PATHS[("float32",) + state.shape[1:] + (shape[4],)]
    assert chosen["path"] == ("kernel" if kernel else "gather") and chosen["why"]
    assert ssd.rows_fit(state.dtype, *state.shape[1:], shape[4]) is kernel

    lanes, begun = np.arange(n), np.asarray(fresh)
    rows = state[lanes, player]
    want_y, want = ssd.ssd_step(*one[:-1], rows * ~fresh[:, None, None, None])
    got = new[lanes, player]
    if kernel:  # the same float32 arithmetic, summed in another order
        np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:       # the lines themselves
        np.testing.assert_array_equal(y, want_y)
        np.testing.assert_array_equal(got, want)
    # a row that begins fresh is ssd_step from zeros, whatever it held
    from_zeros = ssd.ssd_step(*one[:-1], jnp.zeros_like(rows))
    np.testing.assert_allclose(np.asarray(y)[begun], np.asarray(from_zeros[0])[begun],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got)[begun], np.asarray(from_zeros[1])[begun],
                               rtol=1e-6, atol=1e-6)
    # every row that was not addressed: zeros where its lane's game has just
    # begun, else bit for bit what went in
    # (and of the small state the same grid writes rows of: a mixer's conv tail)
    np.testing.assert_array_equal(leaf[lanes, player], rows_in)
    for other in range(1, players):
        for before, after in ((state, new), (tail, leaf)):
            was = np.asarray(before[lanes, (player + other) % players])
            now = np.asarray(after[lanes, (player + other) % players])
            assert not now[begun].any()
            np.testing.assert_array_equal(now[~begun], was[~begun])


def test_a_narrower_state_keeps_the_lines():
    """The state stays float32: a bfloat16 one is another result, and not
    the kernel's to step."""
    assert not ssd.rows_fit(jnp.bfloat16, 2, 128, 64, 128)
    assert "not float32" in ssd.ROW_PATHS[("bfloat16", 2, 128, 64, 128, 1)]["why"]
    # and a row that does not fit VMEM beside its copy
    assert not ssd.rows_fit(jnp.float32, 2, 512, 64, 128)
    assert "VMEM" in ssd.ROW_PATHS[("float32", 2, 512, 64, 128, 1)]["why"]


def test_the_kernel_steps_a_scans_carry_where_it_lies():
    """Inside a ``lax.scan`` whose carry is the state, as the rollout has it:
    three steps with the acting player alternating equal three ``ssd_step``s
    on each player's own rows."""
    shape = (3, 2, 16, 16, 2, 128)
    one, _, _ = _operands(shape, seed=3, dtype=jnp.float32)
    x, dt, A, B, C, state = one
    n = shape[0]
    first = jnp.array([0, 1, 0])
    begun = jnp.zeros((n,), bool)

    def body(carry, t):
        state, leaf = carry
        y, state, leaf = ssd.ssd_step_rows(x * (t + 1), dt, A, B, C, state, (first + t) % 2, begun,
                                           (leaf, jnp.full((n, 3, 128), t + 1.0)))
        return (state, leaf), y

    (got, leaf), ys = jax.jit(lambda *c: jax.lax.scan(body, c, jnp.arange(3)))(
        state, jnp.zeros((n, 2, 3, 128)))
    # each player's tail holds the number of the last step it acted at
    np.testing.assert_array_equal(leaf[:, :, 0, 0], [[3, 2], [2, 3], [3, 2]])
    want = np.asarray(state).copy()
    for t in range(3):
        player = np.asarray((first + t) % 2)
        y, rows = ssd.ssd_step(x * (t + 1), dt, A, B, C, jnp.asarray(want[np.arange(n), player]))
        want[np.arange(n), player] = rows
        np.testing.assert_allclose(ys[t], y, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
