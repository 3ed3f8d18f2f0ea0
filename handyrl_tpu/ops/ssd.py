"""The selective-state scan of a Mamba-2 mixer, in its two forms.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

per head (``x_t`` a head's ``P`` channels, ``B_t``/``C_t`` its group's
``S``-wide input and output maps, ``A < 0`` a scalar per head), with the
state ``S`` (P x S) carried in float32.

* ``ssd_step`` — the recurrence itself, one step (the acting path).
* ``ssd_step_rows`` — the same step on one row a lane of a state kept per
  (lane, player), in place: where one player a lane acts, the other's state
  is neither read nor written.
* ``ssd_chunked`` — a whole window as matrix products (the training path,
  "state-space duality"): inside a chunk of ``chunk`` steps the output is a
  decay-masked ``(C B^T) x`` product, and one state per chunk is passed on.
  The decays are computed in float32 whatever the operands' dtype.

A step with ``dt == 0`` is the identity on the state (``exp(0) = 1`` and
nothing is added), which is how callers skip padding and unobserved steps.
The chunked form is checkpointed: its backward pass recomputes the
chunk-by-chunk decay matrices (heads x chunk x chunk in float32, half a
gigabyte a layer at the benchmark's size) instead of keeping them.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .rows import acting_rows, put_rows


def ssd_step(x, dt, A, B, C, state):
    """x (N, H, P), dt (N, H) float32, A (H,), B and C (N, G, S), state
    (N, H, P, S) float32 -> (y (N, H, P) float32, new state)."""
    n, h, p = x.shape
    g = B.shape[1]
    to_heads = lambda m: jnp.repeat(m.astype(jnp.float32), h // g, axis=1)  # noqa: E731
    decay = jnp.exp(dt * A)[..., None, None]
    added = (dt[..., None] * x.astype(jnp.float32))[..., None] * to_heads(B)[:, :, None, :]
    state = decay * state + added
    return jnp.einsum("nhps,nhs->nhp", state, to_heads(C)), state


CHUNK = 128     # rows of a (heads x head_dim, S) state stepped at once: a square tile
# what a lane's blocks may take of VMEM: the stepped row read and written,
# each double-buffered (the kernel asks for grouped_product's 64 MB scope).
# 128 heads of 64 x 128 in float32 are 4.2 MB a row: 16.8 MB
VMEM_ROWS = 48 << 20
# the static choices made in this process: (dtype, P, H, head, S, G) -> {"path",
# "why", ...}, what the actor host's loop writes out as ``model.ssd_rows_path``
ROW_PATHS: Dict[Tuple, Dict] = {}


def rows_fit(dtype, players: int, heads: int, head_dim: int, state_size: int,
             groups: int = 1) -> bool:
    """Whether ``ssd_step_rows`` steps a state of these rows through its
    kernel: a float32 state ``CHUNK`` wide whose heads are whole tiles of 8
    rows, whose groups are whole chunks of ``CHUNK`` rows, and whose row fits
    VMEM.  From dtype and shape alone; the choice and its reason are kept in
    ``ROW_PATHS``."""
    name = jnp.dtype(dtype).name
    blocks = 2 * 2 * 4 * heads * head_dim * state_size
    refused = (
        (name != "float32", "the state is %s, not float32" % name),
        (state_size != CHUNK, "state_size %d is not %d" % (state_size, CHUNK)),
        (head_dim % 8 or CHUNK % head_dim,
         "head_dim %d is no whole tile of 8 rows that divides %d" % (head_dim, CHUNK)),
        (heads // groups * head_dim % CHUNK, "a group's %d heads of %d are no whole chunks of "
         "%d rows" % (heads // groups, head_dim, CHUNK)),
        (blocks > VMEM_ROWS, "a row's blocks take %d bytes of VMEM, over %d" % (blocks, VMEM_ROWS)),
    )
    why = next((text for failed, text in refused if failed), "")
    ROW_PATHS[(name, players, heads, head_dim, state_size, groups)] = {
        "path": "gather" if why else "kernel",
        "why": why or "float32 rows of %d heads of %d x %d, read and written where they lie" % (
            heads, head_dim, state_size),
        "players": players, "heads": heads, "head_dim": head_dim, "state_size": state_size,
        "groups": groups, "dtype": name}
    return not why


def _rows_kernel(player_ref, fresh_ref, at_ref, decay_ref, x_ref, b_ref, c_ref, s_ref, new_ref,
                 leaf_ref, y_ref, o_ref, t_ref, *, head_dim: int):
    """Grid (phase, lane).  Phase 0: the lane's acting row (heads x head_dim,
    S), read as zeros where the lane's game has just begun, stepped ``CHUNK``
    rows at a time.  A later phase: where the lane's game has just begun the
    block is one of its other rows, to be written as zeros; else it is still
    the block of the step before, and stays.  The same row of the tail's leaf
    (``leaf_ref`` only lends its buffer to ``t_ref``) is written beside the
    state's, with the tail's new rows."""
    from jax.experimental import pallas as pl

    del player_ref, at_ref, leaf_ref    # read by the index maps; aliased
    phase, lane = pl.program_id(0), pl.program_id(1)
    rows, groups = s_ref.shape[0], c_ref.shape[0]
    fresh = fresh_ref[lane] > 0

    @pl.when(phase == 0)
    def _():
        keep = jnp.where(fresh, 0.0, 1.0)
        for c in range(rows // CHUNK):
            lo = c * CHUNK
            g = lo // (rows // groups)
            # a head's decay is a scalar over its ``head_dim`` rows
            kept = jnp.concatenate(
                [(decay_ref[0, (lo + i) // head_dim] * keep) * s_ref[lo + i:lo + i + head_dim]
                 for i in range(0, CHUNK, head_dim)])
            # dt x down the rows (a row of it along the lanes, broadcast down
            # the sublanes and transposed) times B along the lanes
            added = jnp.broadcast_to(x_ref[c:c + 1, :], (CHUNK, CHUNK)).T * b_ref[g:g + 1, :]
            new = kept + added
            o_ref[lo:lo + CHUNK] = new
            # y = S C: the lanes' sum as a sum down the transposed tile's rows,
            # which leaves a chunk's 128 outputs along the lanes
            y_ref[c:c + 1, :] = jnp.sum((new * c_ref[g:g + 1, :]).T, axis=0, keepdims=True)
        t_ref[...] = new_ref[...]

    @pl.when((phase > 0) & fresh)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        t_ref[...] = jnp.zeros_like(t_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_rows(decay, xdt, B, C, state, player, fresh, leaf, new, interpret: bool):
    """decay (N, H), xdt (N, H, head), B and C (N, G, S) float32, state (N, P,
    H, head, S), the tail's leaf (N, P, K, W) and new rows (N, K, W) -> (y
    (N, H, head), state, leaf)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .grouped_product import _VMEM_LIMIT

    n, players, h, p, s = state.shape
    g, rows = B.shape[1], h * p
    # the last lane at or before each whose game has just begun, -1 before the first
    at = jax.lax.cummax(jnp.where(fresh > 0, jnp.arange(n, dtype=jnp.int32), -1))

    def row(phase, lane, player, fresh, at):
        """Phase 0 walks the acting rows.  A later phase stands on the block
        it was handed (the last acting row, or the row the phase before last
        zeroed) until a lane whose game has just begun: then on that lane's
        row ``phase`` players on, and there until the next such lane.  A
        block that does not move is neither fetched nor written again."""
        begun, last = at[lane], at[n - 1]
        handed = jnp.where((phase > 1) & (last >= 0), last, n - 1)
        handed_row = jnp.where((phase > 1) & (last >= 0), player[last] + phase - 1, player[n - 1])
        there = jnp.where(phase == 0, lane, jnp.where(begun >= 0, begun, handed))
        its_row = jnp.where(phase == 0, player[lane],
                            jnp.where(begun >= 0, player[begun] + phase, handed_row))
        return there, its_row % players, 0, 0

    per_lane = lambda *block: pl.BlockSpec(  # noqa: E731
        (None,) + block, lambda phase, lane, player, fresh, at: (lane, 0, 0))
    its_rows = lambda *block: pl.BlockSpec((None, None) + block, row)  # noqa: E731
    y, state, leaf = pl.pallas_call(
        functools.partial(_rows_kernel, head_dim=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(players, n),
            in_specs=[
                pl.BlockSpec((None, 1, h), lambda phase, lane, player, fresh, at: (lane, 0, 0),
                             memory_space=pltpu.SMEM),
                per_lane(rows // CHUNK, CHUNK), per_lane(g, s), per_lane(g, s), its_rows(rows, s),
                per_lane(*new.shape[1:]), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[per_lane(rows // CHUNK, CHUNK), its_rows(rows, s),
                       its_rows(*leaf.shape[2:])]),
        out_shape=[jax.ShapeDtypeStruct((n, rows // CHUNK, CHUNK), jnp.float32),
                   jax.ShapeDtypeStruct((n, players, rows, s), state.dtype),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        # the state and the tail's leaf, counted from the prefetched three
        input_output_aliases={7: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(player, fresh, at, decay[:, None], xdt.reshape(n, rows // CHUNK, CHUNK), B, C,
      state.reshape(n, players, rows, s), new.astype(leaf.dtype), leaf)
    return y.reshape(n, h, p), state.reshape(n, players, rows // p, p, s), leaf


def ssd_step_rows(x, dt, A, B, C, state, player, fresh, tail,
                  interpret: Optional[bool] = None):
    """``ssd_step`` on row ``player[n]`` of lane ``n``'s state, in place: x
    (N, H, P), dt (N, H) float32, A (H,), B and C (N, G, S), state (N, players,
    H, P, S) float32, player (N,) int32, fresh (N,) bool, tail (leaf (N,
    players, K, W), rows (N, K, W)) -> (y (N, H, P) float32, the state with
    that row stepped, the tail's leaf with that row set to ``rows``).  Where
    ``fresh`` the lane's game has just begun: its row is read as zeros and
    its other rows are written as zeros, the tail's too.  Every other row
    keeps its bytes.  The tail is the small state a mixer keeps beside the
    large one (its conv's last inputs), which the caller has read already:
    the kernel's grid writes it on its way (a scatter of its own costs what
    its N serial updates cost, whatever they move).  Where ``rows_fit`` the
    state is never gathered, copied or selected over: a Pallas kernel (the
    interpreter off the TPU) reads each lane's row through the prefetched
    ``player`` and writes it back through ``input_output_aliases``; else
    ``ssd_step``'s lines on the gathered rows (``ops/rows.py``)."""
    if not rows_fit(state.dtype, *state.shape[1:], B.shape[1]):
        y, rows = ssd_step(x, dt, A, B, C, acting_rows(state, player, fresh))
        return y, put_rows(state, rows, player, fresh), put_rows(*tail, player, fresh)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    return _step_rows(jnp.exp(dt * A), dt[..., None] * x.astype(f32), B.astype(f32), C.astype(f32),
                      state, player.astype(jnp.int32), fresh.astype(jnp.int32), *tail, interpret)


ROWS_AT_ONCE = 16   # sequences whose decay matrices are alive together


def ssd_chunked(x, dt, A, B, C, state, chunk: int):
    """x (N, L, H, P), dt (N, L, H) float32, A (H,) float32, B and C
    (N, L, G, S), state (N, H, P, S) float32 -> (y (N, L, H, P) in x's dtype,
    the state after step L - 1).  Sequences go through ``ROWS_AT_ONCE`` at a
    time, so that the decay matrices of a few are alive, not of all."""
    n = x.shape[0]
    if n <= ROWS_AT_ONCE or n % ROWS_AT_ONCE:
        return _ssd_chunked(x, dt, A, B, C, state, chunk)
    split = lambda a: a.reshape((n // ROWS_AT_ONCE, ROWS_AT_ONCE) + a.shape[1:])  # noqa: E731
    y, state = jax.lax.map(
        lambda rows: _ssd_chunked(rows[0], rows[1], A, rows[2], rows[3], rows[4], chunk),
        tuple(split(a) for a in (x, dt, B, C, state)))
    return y.reshape((n,) + y.shape[2:]), state.reshape((n,) + state.shape[2:])


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _ssd_chunked(x, dt, A, B, C, state, chunk: int):
    n, length, h, p = x.shape
    g, s = B.shape[2:]
    r = h // g                                   # heads a group serves
    q = min(int(chunk), length)
    pad = -length % q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    nc = (length + pad) // q
    x = x.reshape(n, nc, q, g, r, p)
    B, C = B.reshape(n, nc, q, g, s), C.reshape(n, nc, q, g, s)
    dt = jnp.moveaxis(dt.reshape(n, nc, q, h), 3, 2)             # (n, nc, h, q)
    cum = jnp.cumsum(dt * A[None, None, :, None], axis=-1)       # log decay since chunk start
    f32 = jnp.float32

    # inside a chunk: y_i += sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    scores = jnp.einsum("ncigs,ncjgs->ncgij", C, B, preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weights = decay.reshape(n, nc, g, r, q, q) * scores[:, :, :, None] \
        * dt.reshape(n, nc, g, r, 1, q)
    y = jnp.einsum("ncgrij,ncjgrp->ncigrp", weights.astype(x.dtype), x,
                   preferred_element_type=f32)

    # what a chunk adds to the state it hands on, and how much of the
    # state it was handed survives it
    to_end = (dt * jnp.exp(cum[..., -1:] - cum)).reshape(n, nc, g, r, q)
    scaled = (x.astype(f32) * jnp.moveaxis(to_end, 4, 2)[..., None]).astype(x.dtype)
    added = jnp.einsum("ncjgrp,ncjgs->ncgrps", scaled, B, preferred_element_type=f32)
    kept = jnp.exp(cum[..., -1]).reshape(n, nc, g, r, 1, 1)
    state = state.reshape(n, g, r, p, s)
    handed = []
    for c in range(nc):                          # one state per chunk is passed on
        handed.append(state)
        state = kept[:, c] * state + added[:, c]
    handed = jnp.stack(handed, axis=1)           # (n, nc, g, r, p, s)

    # across chunks: y_i += exp(cum_i) C_i . (the state handed in)
    carried = jnp.einsum("ncigs,ncgrps->ncigrp", C, handed.astype(C.dtype),
                         preferred_element_type=f32)
    since = jnp.moveaxis(jnp.exp(cum), 3, 2).reshape(n, nc, q, g, r, 1)
    y = (y + since * carried).reshape(n, nc * q, h, p)[:, :length]
    return y.astype(x.dtype), state.reshape(n, h, p, s)
