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
* ``ssd_window`` — a bfloat16 window part's whole core as one Pallas program
  a (row, group): ``ssd_chunked``'s products on a part that is one chunk,
  then the mixer's skip, gate and group norm, with the decay and score tiles
  held in VMEM; ``window_fits`` chooses it from dtype and shape alone, and
  every other part (float32 first of all) keeps ``ssd_chunked`` and the
  mixer's own lines.

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


# -- a bfloat16 window part's whole core, one program a (row, group) ----------

WINDOW_MIN = 16         # steps a part: a bfloat16 tile's rows; a shorter part keeps the lines
HEADS_UNROLLED = 16     # a group's heads are unrolled in the kernels' bodies: at most so many
LANES = 128
# what a block's operands, results, scratch and tiles may take of VMEM (the
# kernels ask for grouped_product's 64 MB scope): the cell's 96 steps of 512
# channels take 5.4 MB, its judge's 184 steps 9.3 MB, and 312 steps are the most
VMEM_WINDOW = 16 << 20
# the static choices made in this process: (dtype, L, H, head, G, S) -> {"path",
# "why", ...}, what ``TrainContext`` writes out as ``model.ssd_window_path``
WINDOW_PATHS: Dict[Tuple, Dict] = {}


def _window_bytes(length: int, channels: int, state_size: int, heads: int) -> int:
    """VMEM a (row, group) block takes in the backward program, the larger
    of the two: per (L, channels) element five bfloat16 blocks double-buffered,
    a float32 and two bfloat16 scratch and eight float32 temporaries of the
    epilogue; B, C and their cotangents; the state, its cotangent and the new
    state's; eight (L, L) float32 tiles; dt and its sums both ways, a head a
    lane (padded to 128) and a head a row."""
    return (60 * length * channels + 16 * length * state_size + 28 * channels * state_size
            + 32 * length * length + 32 * length * (LANES + max(heads, 8)))


def window_fits(dtype, length: int, heads: int, head_dim: int, groups: int,
                state_size: int) -> bool:
    """Whether a window part of these operands runs ``ssd_window``'s kernel:
    bfloat16 (float32 keeps ``ssd_chunked`` and the mixer's lines to the
    bit), ``WINDOW_MIN`` steps or more in whole tiles of 8 rows, a group's
    channels and its state whole 128-lane tiles, at most ``HEADS_UNROLLED``
    heads a group and a block under ``VMEM_WINDOW``.  From dtype and shape
    alone; the choice and its reason are kept in ``WINDOW_PATHS``."""
    name = jnp.dtype(dtype).name
    served = heads // groups
    channels = served * head_dim
    blocks = _window_bytes(length, channels, state_size, served)
    refused = (
        (name != "bfloat16", "the part is %s, not bfloat16" % name),
        (length < WINDOW_MIN, "%d steps a part, under %d" % (length, WINDOW_MIN)),
        (length % 8, "%d steps are not whole tiles of 8 rows" % length),
        (channels % LANES or state_size % LANES, "a group's %d channels and its state of %d are "
         "not whole tiles of %d lanes" % (channels, state_size, LANES)),
        (served > HEADS_UNROLLED,
         "a group serves %d heads, over the %d a program unrolls" % (served, HEADS_UNROLLED)),
        (blocks > VMEM_WINDOW,
         "a block takes %d bytes of VMEM, over %d" % (blocks, VMEM_WINDOW)),
    )
    why = next((text for failed, text in refused if failed), "")
    WINDOW_PATHS[(name, length, heads, head_dim, groups, state_size)] = {
        "path": "lines" if why else "kernel",
        "why": why or "bfloat16 part of %d steps, a group's %d heads of %d x %d in VMEM" % (
            length, served, head_dim, state_size),
        "length": length, "heads": heads, "head_dim": head_dim, "groups": groups,
        "state_size": state_size, "dtype": name}
    return not why


def _contract(a, b, a_axis: int, b_axis: int):
    """``a`` and ``b`` contracted over one axis each, accumulated in float32."""
    return jax.lax.dot_general(a, b, (((a_axis,), (b_axis,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _down(one, rows: int):
    """A (1, 1) value down ``rows`` rows: Mosaic broadcasts one way at a
    time, so what meets a tile goes down the rows first."""
    return jnp.broadcast_to(one, (rows, 1))


def _head(h: int, head_dim: int, scores, cumc_ref, dtr_ref, cumr_ref):
    """Head ``h`` of a block: (its columns, cum down the rows (L, 1), dt
    along the lanes (1, L), its causal decay (L, L): ``exp(cum_i - cum_j)``
    where ``j <= i`` (a difference that is never positive, whatever the
    part's length) and 0 elsewhere, and the float32 weights ``decay x scores
    x dt_j``)."""
    cum_i, dt_j = cumc_ref[:, h:h + 1], dtr_ref[h:h + 1, :]
    i = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    decay = jnp.exp(jnp.where(j <= i, cum_i - cumr_ref[h:h + 1, :], -jnp.inf))
    return slice(h * head_dim, (h + 1) * head_dim), cum_i, dt_j, decay, decay * scores * dt_j


def _scan_block(x_ref, b_ref, c_ref, cumc_ref, dtr_ref, cumr_ref, s_ref, y_ref, head_dim: int):
    """The scan's output of one (row, group) block, ``_ssd_chunked``'s
    products on a part that is one chunk, their operands rounded where its
    lines round them: head by head into ``y_ref`` (L, channels) float32.  ->
    (scores ``C B^T`` (L, L), carried ``C . state`` (L, channels)), which the
    group's heads share."""
    B, C = b_ref[...], c_ref[...]
    scores = _contract(C, B, 1, 1)
    carried = _contract(C, s_ref[...].astype(C.dtype), 1, 1)
    for h in range(dtr_ref.shape[0]):
        cols, cum_i, _, _, weights = _head(h, head_dim, scores, cumc_ref, dtr_ref, cumr_ref)
        x = x_ref[:, cols]
        y_ref[:, cols] = _contract(weights.astype(x.dtype), x, 1, 0) \
            + jnp.exp(cum_i) * carried[:, cols]
    return scores, carried


def _to_end(h: int, dtc_ref, cumc_ref):
    """Head ``h``: (cum at the last step (1, 1), whose ``exp`` is what
    survives the part of the state handed in, ``exp(cum_last - cum_j)``
    (L, 1), and that times dt_j: what step j adds to the state handed on)."""
    length = cumc_ref.shape[0]
    last = cumc_ref[length - 1:length, h:h + 1]
    end = jnp.exp(_down(last, length) - cumc_ref[:, h:h + 1])
    return last, end, dtc_ref[:, h:h + 1] * end


def _window_state_kernel(x_ref, b_ref, dtc_ref, cumc_ref, s_ref, new_ref, *, head_dim):
    """The state a (row, group) block hands on, a program of its own so that
    a caller who drops the state (a train step's forward part) runs none:
    ``exp(cum_last) state + sum_j to_end_j x_j B_j^T``, head by head (rows
    of the state are the columns of x)."""
    B = b_ref[...]
    for h in range(dtc_ref.shape[1]):
        cols = slice(h * head_dim, (h + 1) * head_dim)
        last, _, to_end = _to_end(h, dtc_ref, cumc_ref)
        x = x_ref[:, cols]
        scaled = (x.astype(jnp.float32) * to_end).astype(x.dtype)
        new_ref[cols, :] = jnp.exp(_down(last, head_dim)) * s_ref[cols, :] \
            + _contract(scaled, B, 0, 0)


def _gated(y_ref, x_ref, z_ref, skip_ref, eps: float):
    """(x, z, the gate's sigmoid, skipped ``y + D x``, gated ``skipped x
    silu(z)``, the group's ``1 / rms`` (L, 1)), all float32."""
    x, z = x_ref[...].astype(jnp.float32), z_ref[...].astype(jnp.float32)
    opened = jax.nn.sigmoid(z)
    skipped = y_ref[...] + skip_ref[...] * x
    gated = skipped * (z * opened)
    return x, z, opened, skipped, gated, jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=1, keepdims=True) + eps)


def _window_forward_kernel(x_ref, z_ref, b_ref, c_ref, dtc_ref, cumc_ref, dtr_ref, cumr_ref,
                           skip_ref, scale_ref, s_ref, o_ref, y_ref, *, head_dim, eps):
    del dtc_ref
    _scan_block(x_ref, b_ref, c_ref, cumc_ref, dtr_ref, cumr_ref, s_ref, y_ref, head_dim)
    *_, gated, inverse = _gated(y_ref, x_ref, z_ref, skip_ref, eps)
    o_ref[...] = (gated * inverse * scale_ref[...]).astype(o_ref.dtype)


def _window_backward_kernel(x_ref, z_ref, b_ref, c_ref, dtc_ref, cumc_ref, dtr_ref, cumr_ref,
                            skip_ref, scale_ref, s_ref, do_ref, *refs, head_dim, eps):
    """The block's decays and pre-norm output again, then every cotangent:
    of dt and of its cumulative sum down the rows (what sums over a row's
    lanes) and along the lanes (what sums over a column), which the wrapper
    adds; of ``skip`` and ``norm_scale`` this program's share.  ``refs``
    begin with the new state's cotangent where the caller used that state:
    without one (a train step's forward part) its terms are not computed."""
    dnew_ref = refs[0] if len(refs) == 15 else None
    (dx_ref, dz_ref, db_ref, dc_ref, ds_ref, ddtc_ref, dcumc_ref, ddtr_ref, dcumr_ref, dskip_ref,
     dscale_ref, y_ref, dcar_ref, xs_ref) = refs[-14:]
    f32, narrow = jnp.float32, x_ref.dtype
    length = x_ref.shape[0]
    B, C = b_ref[...], c_ref[...]
    scores, carried = _scan_block(x_ref, b_ref, c_ref, cumc_ref, dtr_ref, cumr_ref, s_ref, y_ref,
                                  head_dim)
    x, z, opened, skipped, gated, inverse = _gated(y_ref, x_ref, z_ref, skip_ref, eps)
    # the norm, the gate, the skip
    d_out = do_ref[...].astype(f32)
    dscale_ref[...] = jnp.sum(d_out * gated * inverse, axis=0, keepdims=True)
    scaled = d_out * scale_ref[...]
    d_gated = inverse * (scaled - gated * (inverse * inverse) * jnp.mean(
        scaled * gated, axis=1, keepdims=True))
    dz_ref[...] = (d_gated * skipped * opened * (1.0 + z * (1.0 - opened))).astype(dz_ref.dtype)
    y_ref[...] = d_gated * (z * opened)         # the scan's output's cotangent, in its place
    dskip_ref[...] = jnp.sum(y_ref[...] * x, axis=0, keepdims=True)
    if dnew_ref is not None:
        d_new = dnew_ref[...].astype(narrow)
        d_scaled = _contract(B, d_new, 1, 1)    # (L, channels): of ``scaled``, every head's
    d_scores = jnp.zeros_like(scores)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (length, 1), 0) == length - 1
    ddt_i, dcum_i = [], []
    for h in range(dtr_ref.shape[0]):
        cols, cum_i, dt_j, decay, weights = _head(h, head_dim, scores, cumc_ref, dtr_ref, cumr_ref)
        x_h, xf_h = x_ref[:, cols], x[:, cols]
        dy = y_ref[:, cols]
        dy_n = dy.astype(narrow)
        # across the part's start: y += exp(cum_i) C . state
        since = jnp.exp(cum_i)
        dcum = jnp.sum(dy * since * carried[:, cols], axis=1, keepdims=True)
        dcar_ref[:, cols] = (since * dy).astype(narrow)
        dx = skip_ref[:, cols] * dy
        if dnew_ref is None:
            ddt_i.append(jnp.zeros_like(dcum))
        else:   # the state handed on: exp(last) state + sum_j to_end_j x_j B_j^T
            last, end, to_end = _to_end(h, dtc_ref, cumc_ref)
            xs_ref[:, cols] = (xf_h * to_end).astype(narrow)
            d_to_end = jnp.sum(d_scaled[:, cols] * xf_h, axis=1, keepdims=True)
            moved = d_to_end * to_end
            ds_ref[cols, :] = jnp.exp(_down(last, head_dim)) * dnew_ref[cols, :]
            d_last = jnp.sum(moved, keepdims=True) + jnp.exp(last) * jnp.sum(
                dnew_ref[cols, :] * s_ref[cols, :], keepdims=True)
            dcum = dcum - moved + jnp.where(last_row, d_last, 0.0)
            ddt_i.append(d_to_end * end)
            dx = dx + d_scaled[:, cols] * to_end
        # inside the part: y += (decay x scores x dt_j) x
        masked = _contract(dy_n, x_h, 1, 1) * decay
        d_scores = d_scores + masked * dt_j
        masked = masked * scores
        ddt_j = jnp.sum(masked, axis=0, keepdims=True)
        ddtr_ref[h:h + 1, :] = ddt_j
        dcumr_ref[h:h + 1, :] = -(dt_j * ddt_j)
        dcum_i.append(dcum + jnp.sum(masked * dt_j, axis=1, keepdims=True))
        dx_ref[:, cols] = (dx + _contract(weights.astype(narrow), dy_n, 0, 0)).astype(dx_ref.dtype)
    ddtc_ref[...] = jnp.concatenate(ddt_i, axis=1)
    dcumc_ref[...] = jnp.concatenate(dcum_i, axis=1)
    d_scores, d_carried = d_scores.astype(narrow), dcar_ref[...]
    dc_ref[...] = (_contract(d_scores, B, 1, 0) + _contract(
        d_carried, s_ref[...].astype(narrow), 1, 0)).astype(dc_ref.dtype)
    if dnew_ref is None:
        db_ref[...] = _contract(d_scores, C, 0, 0).astype(db_ref.dtype)
        ds_ref[...] = _contract(d_carried, C, 0, 0)
    else:
        db_ref[...] = (_contract(d_scores, C, 0, 0)
                       + _contract(xs_ref[...], d_new, 1, 0)).astype(db_ref.dtype)
        ds_ref[...] += _contract(d_carried, C, 0, 0)


def _window_call(kernel, operands, outs, scratch, groups: int, interpret: bool):
    """One program a (row, group) over ``operands`` -> arrays of the ``outs``
    shapes.  An array (n, groups, ...) is blocked by its two leading axes;
    (n, rows, columns) by row and, its columns, by group; (1, columns) is
    every row's, its columns by group."""
    from jax.experimental import pallas as pl

    from .grouped_product import _call

    def spec(a):
        if len(a.shape) == 4:
            return pl.BlockSpec((None, None) + a.shape[2:], lambda n, g: (n, g, 0, 0))
        if len(a.shape) == 2:
            return pl.BlockSpec((1, a.shape[1] // groups), lambda n, g: (0, g))
        return pl.BlockSpec((None, a.shape[1], a.shape[2] // groups), lambda n, g: (n, 0, g))

    return _call(kernel, (), (operands[0].shape[0], groups), [spec(a) for a in operands],
                 [spec(a) for a in outs], outs, scratch, interpret, *operands)


def _by_group(a, groups: int, along_lanes: bool = False):
    """(n, L, H) -> (n, G, L, H / G), a group's heads down the lanes of its
    block; ``along_lanes``: (n, G, H / G, L), the steps along the lanes."""
    n, length, heads = a.shape
    a = a.reshape(n, length, groups, heads // groups)
    return a.transpose(0, 2, 3, 1) if along_lanes else a.transpose(0, 2, 1, 3)


def _window_operands(x, dt, cum, B, C, z, skip, scale, state, groups: int):
    """The kernels' common operands, in their order: a head's dt and
    cumulative sum are read down the rows (against a step's x) and along the
    lanes (against a column of the decays), so both lie both ways; the
    skip is said once a channel."""
    n, heads, channels = x.shape[0], dt.shape[2], x.shape[2]
    return (x, z, B, C, _by_group(dt, groups), _by_group(cum, groups),
            _by_group(dt, groups, True), _by_group(cum, groups, True),
            jnp.repeat(skip, channels // heads)[None], scale[None],
            state.reshape(n, groups, channels // groups, state.shape[-1]))


def _window_forward(x, dt, cum, B, C, z, skip, scale, state, groups, eps, interpret):
    from jax.experimental.pallas import tpu as pltpu

    length, channels = x.shape[1:]
    head_dim = channels // dt.shape[2]
    operands = _window_operands(x, dt, cum, B, C, z, skip, scale, state, groups)
    (out,) = _window_call(
        functools.partial(_window_forward_kernel, head_dim=head_dim, eps=eps),
        operands, [jax.ShapeDtypeStruct(x.shape, x.dtype)],
        [pltpu.VMEM((length, channels // groups), jnp.float32)], groups, interpret)
    # the state handed on, by a program a caller who drops it never runs
    (new,) = _window_call(
        functools.partial(_window_state_kernel, head_dim=head_dim),
        (x, B, operands[4], operands[5], operands[-1]),
        [jax.ShapeDtypeStruct(operands[-1].shape, jnp.float32)], [], groups, interpret)
    return out, new.reshape(state.shape)


def _window_backward(x, dt, cum, B, C, z, skip, scale, state, d_out, d_new, groups, eps,
                     interpret):
    """``d_new`` None: the caller dropped the new state."""
    from jax.experimental.pallas import tpu as pltpu

    n, length, channels = x.shape
    heads, f32 = dt.shape[2], jnp.float32
    operands = _window_operands(x, dt, cum, B, C, z, skip, scale, state, groups)
    like = lambda a, dtype=None: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)  # noqa: E731
    by_rows, by_lanes = operands[4], operands[6]
    block = (length, channels // groups)
    handed_on = () if d_new is None else (d_new.reshape(operands[-1].shape),)
    dx, dz, dB, dC, d_state, ddt_i, dcum_i, ddt_j, dcum_j, d_skip, d_scale = _window_call(
        functools.partial(_window_backward_kernel, head_dim=channels // heads, eps=eps),
        operands + (d_out,) + handed_on,
        [like(x), like(z), like(B), like(C), like(operands[-1]), like(by_rows), like(by_rows),
         like(by_lanes), like(by_lanes), jax.ShapeDtypeStruct((n, 1, channels), f32),
         jax.ShapeDtypeStruct((n, 1, channels), f32)],
        [pltpu.VMEM(block, f32), pltpu.VMEM(block, x.dtype), pltpu.VMEM(block, x.dtype)],
        groups, interpret)
    # what a head's rows and its lanes each summed, added as (n, L, H)
    whole = lambda i, j: (i.transpose(0, 2, 1, 3) + j.transpose(0, 3, 1, 2)).reshape(dt.shape)  # noqa: E731
    return (dx, whole(ddt_i, ddt_j), whole(dcum_i, dcum_j), dB, dC, dz,
            d_skip.reshape(n, heads, -1).sum(axis=(0, 2)), d_scale.sum(axis=(0, 1)),
            d_state.reshape(state.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _window(x, dt, cum, B, C, z, skip, scale, state, groups, eps, interpret):
    return _window_forward(x, dt, cum, B, C, z, skip, scale, state, groups, eps, interpret)


def _window_fwd(x, dt, cum, B, C, z, skip, scale, state, groups, eps, interpret):
    kept = tuple(a.value for a in (x, dt, cum, B, C, z, skip, scale, state))
    return _window_forward(*kept, groups, eps, interpret), kept


def _window_bwd(groups, eps, interpret, kept, cotangents):
    zero = jax.custom_derivatives.SymbolicZero
    d_out, d_new = cotangents
    if isinstance(d_out, zero):
        d_out = jnp.zeros(d_out.shape, d_out.dtype)
    return _window_backward(*kept, d_out, None if isinstance(d_new, zero) else d_new, groups, eps,
                            interpret)


_window.defvjp(_window_fwd, _window_bwd, symbolic_zeros=True)


def _running_sum(a):
    """(N, L, H) float32 summed along its steps up to each, as a product
    with a triangle of ones at full precision: XLA's ``cumsum`` along a
    middle axis took a millisecond a call at the cell's (64, 96, 64), a
    quarter of the kernels' own time."""
    lower = jnp.tril(jnp.ones((a.shape[1],) * 2, a.dtype))
    return jnp.einsum("ij,njh->nih", lower, a, precision=jax.lax.Precision.HIGHEST)


def ssd_window(x, dt, A, B, C, z, skip, scale, state, eps: float,
               interpret: Optional[bool] = None):
    """A bfloat16 window part's core where ``window_fits``: x (N, L, H, P),
    dt (N, L, H) float32, A (H,) float32, B and C (N, L, G, S), the gate z
    (N, L, H x P), the mixer's ``D`` (H,) and ``norm_scale`` (H x P,), state
    (N, H, P, S) float32 -> (the mixer's normed output (N, L, H x P) in x's
    dtype, the state after step L - 1): ``ssd_chunked`` on a part that is
    one chunk, ``+ D x``, ``x silu(z)`` and the RMS norm over a group's
    channels, in one Pallas program a (row, group) (the interpreter off the
    TPU) that keeps the (L, L) scores and decays and the float32 epilogue in
    VMEM.  Products accumulate in float32 and round their operands where the
    lines do; the backward pass is a second program that computes the decays
    and the pre-norm output again from the same operands."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, length, heads, head_dim = x.shape
    flat = lambda a: a.reshape(n, length, -1)  # noqa: E731
    f32 = jnp.float32
    out, new = _window(
        flat(x), dt, _running_sum(dt * A), flat(B), flat(C), z, skip.astype(f32),
        scale.astype(f32), state.reshape(n, heads * head_dim, -1), B.shape[2], float(eps),
        bool(interpret))
    return out, new.reshape(state.shape)
