"""Grouped matrix products over a row buffer in blocks of equal height:
each block's rows are multiplied by the weights of the one group (expert)
the block belongs to, read where they lie.

``owner`` (blocks,) int32 names each block's group and is non-decreasing:
a group's blocks are consecutive.  A block's height is the buffer's rows
over ``owner``'s length, the caller's to choose: ``BLOCK`` where a group's
rows fill the MXU's tile, ``FEW_ROWS`` where a group gets a handful and a
taller block would be padding that is written, read and multiplied
(``ops/routed_experts.py`` ``block_rows``).  It is handed to the kernels as a
prefetched scalar array that the weight operand's index map reads, so no
block's weights are copied out.

``live`` (a traced int32 scalar, prefetched beside ``owner``) is the number of
leading blocks that hold a row: a caller that lays its rows out from the
buffer's start (``ops/routed_experts.py``) knows it before the kernels run,
and the empty blocks are a suffix.  A grid step whose block is at or past
``live`` does nothing: no product, and no bytes either, since its operands'
index maps stand on the last live block (no new tile is fetched) and its
output's stands on the first empty block, which is written back once a column
tile with whatever the output's VMEM tile held.  **What a kernel returns for
the rows of a block at or past ``live`` is uninitialised memory**: a caller
takes those rows by selection, never into a sum or a product.  The work is
the routing's, not the buffer's; without ``live`` every block is live.

A ``period`` (a traced int32 scalar, prefetched beside ``owner``) is one more
coordinate of the same maps: the weights are then a stack over periods,
``(periods, groups, k, n)``, of which the kernels read period ``period`` where
it lies and write that period of the stacked gradient they are handed, the
other periods' bytes left as they are.  A scan over periods hands a kernel no
copy that way: XLA fuses a ``dynamic-slice`` of a stacked operand into a
product of its own and cannot fuse one into a custom call, so each period's
weights were copied out of the stack four times an update and each period's
gradient copied into a stacked one (PERF.md, PR 51).

* ``grouped_dot(x, w, owner)``: x (m, k), w (groups, k, n) -> (m, n) float32,
  ``out[block b] = x[block b] @ w[owner[b]]`` for ``b < live``.  The grid runs the row blocks
  innermost, so a group's weight tile stays in VMEM across its blocks and is
  read once a column tile.  Differentiable: the rows' cotangent is the same
  kernel through the transposed weights (``w`` read as it lies), the
  weights' gradient ``_weight_sums``.
* ``_weight_sums(x, dy, owner, groups)``: (groups, k, n),
  ``out[g] = sum over g's live blocks of x[block]^T @ dy[block]``, accumulated
  in float32 in VMEM and written once a group, by the kernel, zeros for a group
  with no live block.  Handed the sum a loop over passes carries (``into``,
  ``first``; ``grouped_dot``'s ``into``), it writes into that sum's own
  buffer: the first pass its sums alone (of what the buffer held it fetches
  one tile and uses none), a later pass ``into[g]`` plus them, added in
  float32 before the one cast.  So the loop's body holds no pass of XLA's
  over an array of the weights' shape, and the usual update, one pass, pays
  for none (PERF.md, PR 50).  With a ``period`` ``into`` is the stacked sum
  ``(periods, groups, k, n)`` and the same holds of its period ``period``.

Operands go to the MXU as they are handed over (bf16 in the train step) and
accumulate in float32.  Pallas on the TPU, the Pallas interpreter elsewhere
(``interpret=None`` picks, as ``ops/flash_attention.py`` does).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

BLOCK = 128                 # rows of a block: the MXU's tile
FEW_ROWS = 16               # the least a block can be: a bfloat16 tile's sublanes
TILE = 4096                 # most columns of a weight tile held in VMEM
_VMEM_LIMIT = 64 << 20      # over the compiler's default scope: a weight tile is double-buffered
# what ``_weight_sums``' float32 sum and its double-buffered output tile may
# take of that scope, 8 bytes an element of the tile in bfloat16, 10 with a
# carried sum's single-buffered tile beside them: a (2048, 4096) tile (gated
# experts of width 2,048 on a 2,048-wide stream) is 64 MB, 84 with the
# carried sum's, and is refused by the compiler, so its columns go in two
# tiles of 42 MB; (2688, 1856) fits whole at 50 MB with the carried sum's
# (its 1,856 columns could not be halved: were that tile double-buffered
# too, 12 bytes an element, it would not fit), (4096, 1536) whole without
_SUMS_BYTES = 56 << 20


def _tile(n: int) -> int:
    """A dimension whole where it fits a tile, else tiles of ``TILE`` (the
    last one partial: what lies past an array's edge is never written)."""
    return min(n, TILE)


def _call(kernel, prefetched, grid, in_specs, out_spec, out_shape, scratch, interpret, *operands,
          aliases=None):
    """``pallas_call`` whose first ``len(prefetched)`` operands are int32
    vectors prefetched to SMEM: the index maps and the kernel read them.
    ``aliases``: operand (counted with the prefetched) -> the output whose
    buffer it is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched), grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*prefetched, *operands)


def _scalar(value):
    """A scalar as the (1,) int32 vector that is prefetched."""
    return jnp.reshape(value, 1).astype(jnp.int32)


def _at(period):
    """What a stacked operand's index maps are handed beside the other
    prefetched vectors: the period, (1,) int32; nothing without one."""
    return () if period is None else (_scalar(period),)


def _count(live, blocks: int):
    """``live`` as the kernels are handed it, (1,) int32 within [0, blocks];
    None: every block."""
    return _scalar(blocks if live is None else jnp.clip(live, 0, blocks))


def _stands(b, live):
    """The block whose operands step ``b`` holds: its own, past the live
    blocks the last of them (block 0 where none is), so a step that does
    nothing fetches nothing."""
    return jnp.minimum(b, jnp.maximum(live[0] - 1, 0))


def _rows_kernel(owner_ref, live_ref, *refs, transposed: bool):
    from jax.experimental import pallas as pl

    x_ref, w_ref, o_ref = refs[-3:]     # before them the period, which the index maps read
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], dims, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transposed", "out_dtype", "interpret"))
def _rows_times(x, w, owner, transposed: bool, out_dtype, interpret: bool, period=None,
                live=None):
    """out[block b] = x[block b] @ w[owner[b]] (``transposed``: @ w[owner[b]]^T)
    for b < ``live``, the other blocks' rows uninitialised (of them the
    first is written, with what the output's tile held; the rest are not
    touched); with a ``period`` w is (periods, groups, ...) and read at that
    period.  Jitted, as ``_weight_sums`` is: a net calls each at a few shapes many
    times (layers, window parts, the replay), and a jitted callee is traced
    and lowered to its kernel once a shape, not once a call."""
    from jax.experimental import pallas as pl

    (m, k), n = x.shape, w.shape[-2 if transposed else -1]
    tn, rows = _tile(n), m // owner.size
    at = _at(period)
    stacked = (None,) * len(at)     # the stack's dimension, at the period: ``t`` is () or (period,)
    if transposed:
        w_spec = pl.BlockSpec(
            stacked + (None, tn, k),
            lambda j, b, owner, live, *t: (*(p[0] for p in t), owner[_stands(b, live)], j, 0))
    else:
        w_spec = pl.BlockSpec(
            stacked + (None, k, tn),
            lambda j, b, owner, live, *t: (*(p[0] for p in t), owner[_stands(b, live)], 0, j))
    return _call(
        functools.partial(_rows_kernel, transposed=transposed),
        (owner, _count(live, owner.size), *at),
        (pl.cdiv(n, tn), owner.size),      # row blocks innermost: a weight tile stays
        [pl.BlockSpec((rows, k), lambda j, b, owner, live, *_: (_stands(b, live), 0)), w_spec],
        # the steps past the live blocks park on the first empty one
        pl.BlockSpec((rows, tn), lambda j, b, owner, live, *_: (jnp.minimum(b, live[0]), j)),
        jax.ShapeDtypeStruct((m, n), out_dtype), [], interpret, x, w)


def _sums_kernel(group_ref, block_ref, first_ref, live_ref, *refs, stacked: bool):
    """``refs``: the rows' and their cotangent's blocks, the output's tile and
    the float32 sum; with a carried sum, before the last two that sum's
    tile; where that sum is ``stacked``, first of all the prefetched period
    (read by the index maps alone)."""
    from jax.experimental import pallas as pl

    x_ref, dy_ref, *refs = refs[1:] if stacked else refs
    into_ref, o_ref, acc_ref = refs if len(refs) == 3 else (None, *refs)
    step, last = pl.program_id(2), pl.num_programs(2) - 1
    group = group_ref[step]
    opens = (step == 0) | (group_ref[jnp.maximum(step - 1, 0)] != group)
    closes = (step == last) | (group_ref[jnp.minimum(step + 1, last)] != group)
    first = first_ref[0] != 0

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a group's last step holds no block, and a block past the live ones no row
    @pl.when(jnp.logical_not(closes) & (block_ref[step] < live_ref[0]))
    def _():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(closes & first)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    if into_ref is not None:
        # in strips of rows under a loop: written out whole, as the first pass's cast is,
        # this rare branch was 0.3 MB more of every call site's code (PERF.md, PR 50)
        strip = math.gcd(acc_ref.shape[0], 256)
        strip = strip if strip % 16 == 0 else acc_ref.shape[0]     # whole packed bfloat16 sublanes

        @pl.when(closes & jnp.logical_not(first))
        def _():
            def add(r, _):
                rows = pl.ds(pl.multiple_of(r * strip, strip), strip)
                o_ref[rows, :] = (acc_ref[rows, :] + into_ref[rows, :].astype(jnp.float32)
                                  ).astype(o_ref.dtype)

            jax.lax.fori_loop(0, acc_ref.shape[0] // strip, add, None)


@functools.partial(jax.jit, static_argnames=("groups", "out_dtype", "interpret"))
def _weight_sums(x, dy, owner, groups: int, out_dtype, interpret: bool, into=None, first=None,
                 period=None, live=None):
    """out[g] = sum over the blocks b < ``live`` with owner[b] == g of x[b]^T @ dy[b];
    with ``into`` (groups, k, n) in ``out_dtype`` and ``first`` () bool, the
    sum a loop carries: ``into``'s buffer is the output's, and out[g] is that
    sum alone where ``first`` (whatever ``into`` holds), else ``into[g]`` plus
    it, added in float32 before the one cast.  With a ``period``, ``into`` is
    (periods, groups, k, n) and all of that is said of ``out[period]``: no
    other period's tile is on the grid, so what ``into`` held there stays.

    The grid's innermost axis walks each group's blocks and then one step
    more that holds no block and writes the group's sum out: so a group
    with no block is written too, as zeros (past the first pass as
    ``into[g]`` was), and no pass over the output follows the kernel.  Step
    ``s`` of group ``g`` comes after one such step of each earlier group: it
    is block ``s - g``, and where that is at or past ``live`` the step adds
    nothing and its operands' index maps stand on the last live block.
    ``into``'s tile is single-buffered, and where
    ``first`` its index map stands on the first tile whatever the step: the
    usual update, one pass, reads that one tile and not the sum."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n, blocks = x.shape, dy.shape[1], owner.size
    tk, tn, rows = _tile(k), _tile(n), m // blocks
    while (8 if into is None else 10) * tk * tn > _SUMS_BYTES and tn % 256 == 0:
        tn //= 2    # more column tiles: the rows' blocks are read once more each
    group = jnp.sort(jnp.concatenate([owner, jnp.arange(groups, dtype=owner.dtype)]))
    block = jnp.minimum(jnp.arange(blocks + groups, dtype=owner.dtype) - group, blocks - 1)
    at = _at(period)
    stacked = (None,) * len(at)     # the stack's dimension, at the period: ``t`` is () or (period,)
    out = jax.ShapeDtypeStruct((groups, k, n) if period is None else into.shape, out_dtype)
    in_specs = [
        pl.BlockSpec((rows, tk),
                     lambda i, j, s, group, block, first, live, *_: (_stands(block[s], live), i)),
        pl.BlockSpec((rows, tn),
                     lambda i, j, s, group, block, first, live, *_: (_stands(block[s], live), j))]
    operands, aliases = (x, dy), None
    if into is None:
        first = True
    else:
        assert (into.shape[-3:], into.dtype) == ((groups, k, n), out.dtype), (into.shape, into.dtype)
        in_specs.append(pl.BlockSpec(
            stacked + (None, tk, tn), lambda i, j, s, group, block, first, live, *t: tuple(
                jnp.where(first[0] != 0, 0, index)
                for index in (*(p[0] for p in t), group[s], i, j)),
            pipeline_mode=pl.Buffered(1)))
        operands, aliases = (x, dy, into), {6 + len(at): 0}    # counted from the prefetched
    return _call(
        functools.partial(_sums_kernel, stacked=bool(at)),
        (group, block, _scalar(first), _count(live, blocks), *at),
        (pl.cdiv(k, tk), pl.cdiv(n, tn), blocks + groups), in_specs,
        pl.BlockSpec(stacked + (None, tk, tn),
                     lambda i, j, s, group, block, first, live, *t: (
                         *(p[0] for p in t), group[s], i, j)),
        out, [pltpu.VMEM((tk, tn), jnp.float32)], interpret, *operands, aliases=aliases)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(x, w, owner, interpret: Optional[bool] = None, into=None, period=None,
                live=None):
    """x (m, k) in ``owner.size`` blocks of equal height, w (groups, k, n),
    owner (blocks,) int32 non-decreasing -> (m, n) float32: each block's
    rows times its group's weights.  ``live`` () int32: the blocks before it
    are computed, forward and backward; the rows of the others come back
    uninitialised, in the output and in ``x``'s cotangent, and add nothing to
    ``w``'s (the module's text).  ``into``: (a sum of ``w``'s shape and
    dtype that the caller's loop carries, first () bool); ``w``'s cotangent
    is then that sum with this call's gradient added (where ``first``: the
    gradient alone, whatever the sum holds), in the sum's own buffer.
    ``period`` () int32: w is (periods, groups, k, n), the weights are its
    period ``period``, and of ``w``'s cotangent, the stacked sum, this call
    writes that period alone (with no ``into``: into zeros)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _rows_times(x, w, owner, False, jnp.float32, interpret, period, live)


def _grouped_fwd(x, w, owner, interpret, into, period, live):
    return (grouped_dot(x, w, owner, interpret, None, period, live),
            (x, w, owner, into, period, live))


def _grouped_bwd(interpret, saved, dy):
    x, w, owner, into, period, live = saved
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if period is not None and into is None:
        into = (jnp.zeros_like(w), True)
    dy = dy.astype(x.dtype)     # the MXU's operand, as the weights are
    return (_rows_times(dy, w, owner, True, x.dtype, interpret, period, live),
            _weight_sums(x, dy, owner, w.shape[-3], w.dtype, interpret, *(into or ()),
                         period=period, live=live),
            None, None, None, None)


grouped_dot.defvjp(_grouped_fwd, _grouped_bwd)
