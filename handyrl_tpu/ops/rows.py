"""A recurrent state kept per (lane, player), ``(N, players, ...)`` a leaf,
where exactly one player a lane acts: the acting player's row of each lane is
read, stepped and written back, and the lane's other rows are neither read
nor written, but for the zeros a game starts from.  Plain gathers and
scatters, for the leaves that are small; the large one has a kernel
(``ops/ssd.py`` ``ssd_step_rows``).

``fresh`` (N,) bool says the lane's game has just begun: every player's
state is zeros then.  The acting row is zeroed in the read; the others by a
scatter whose index lies past the edge where nothing begins (``mode="drop"``),
so it writes where and when a game begins only: no pass over the leaf.
"""

from __future__ import annotations

import jax.numpy as jnp

# the ``jax.named_scope`` round these gathers and scatters in a program: a
# component of their ops' ``op_name`` in a device profile
# (``runtime/device_rollout.py`` has the rule, and the whole-tree passes that
# bear the same name where more than one player a lane observes)
COMMIT_SCOPE = "state_commit"


def acting_rows(leaf, player, fresh):
    """(N, players, ...) -> (N, ...): row ``player[n]`` of lane ``n``, as
    zeros where ``fresh[n]``."""
    rows = leaf[jnp.arange(leaf.shape[0]), player]
    return rows * ~fresh.reshape((-1,) + (1,) * (rows.ndim - 1))


def begin_rows(leaf, player, fresh, whole: bool = False):
    """``leaf`` with lane ``n``'s rows other than ``player[n]`` (``whole``:
    all its rows) written as zeros where ``fresh[n]``."""
    n, players = leaf.shape[:2]
    lanes = jnp.where(fresh, jnp.arange(n), n)
    if whole:
        return leaf.at[lanes].set(0, mode="drop")
    for other in range(1, players):
        leaf = leaf.at[lanes, (player + other) % players].set(0, mode="drop")
    return leaf


def put_rows(leaf, rows, player, fresh):
    """``leaf`` with row ``player[n]`` of lane ``n`` set to ``rows[n]``, and
    the lane's other rows to zeros where ``fresh[n]``."""
    return begin_rows(leaf.at[jnp.arange(leaf.shape[0]), player].set(rows), player, fresh)
