"""A routed expert layer's two halves: the router's choice, and the part of
the mix that the experts held here give.

The layer is told which experts it holds (``w1``/``w2`` carry ``held`` of
them, ``offset`` is the first one's index among all).  It scores and chooses
over all experts, normalises the gates over all the chosen (where the
router's family does: ``choose``), and adds only its own experts' terms: what expert parallelism asks of one chip.  On one
chip it runs without the exchange; nothing stands in for the absent chips.

No token is dropped at any imbalance.  The (token, choice) pairs that fall
on held experts are sorted by expert into a row buffer in which every
expert's rows start on a block, so a block belongs to one expert.  A block's
height follows the rows an expert gets (``block_rows``: ``BLOCK`` rows where
a uniform router fills one, ``FEW_ROWS`` where it gives an expert a handful,
as a rollout step of a few dozen rows does: the buffer is written, read and
multiplied whole, and at 128 rows a held expert's block of padding was 97%
of it, PERF.md, PR 46).  The two products are grouped products over the
blocks, each block
against its expert's weights read where they lie (``ops/grouped_product.py``:
a Pallas kernel whose weight operand is indexed by the block's expert; its
transpose sums the blocks' weight gradients once an expert).  That kernel
takes bfloat16 operands; float32 operands (the judge's forward, tests) keep
the plain ``jnp`` block products, at the precision their caller asks.
Shapes are static: the buffer holds ``SHARES`` times a uniform router's
share of rows and a block of padding for each held expert.  The work is the
routing's: every expert's rows start on a block, so the blocks that hold a
row are the buffer's first ``ends[-1] / block`` and the empty ones a suffix;
``_one_pass`` counts the live ones (``live``) and the kernels' grid steps
past them do nothing and move no bytes, forward, in the replay and in both
backward kernels (the step's time follows the rows the routers send: PERF.md,
PRs 59 and 60; in blocks of ``FEW_ROWS`` every block is still run,
``_skips``).  What the kernels return for those blocks' rows is
uninitialised memory, so everything here that sums over or gathers from a
buffer row takes it by selection on ``used`` or on the pair's ``live``, never
by a product with a zero gate.  An update whose rows outgrow the buffer takes
further passes over it, as many as its rows need (``_passes``: a
``lax.while_loop`` forward and backward, so the worst case, every token
choosing ``min(top_k, held)`` held experts, costs time and no memory).
A caller that scans over equal periods hands ``held_mix`` the periods'
stacked weights and the period, and the kernels read and write that period
where it lies (``_passes_at``, ``open_sinks``; PERF.md, PR 51).
Filling the buffer and summing a token's rows back are each other's
transpose and are written as gathers both ways (``_dispatch`` /
``_combine``), so no scatter runs forward or backward.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .grouped_product import BLOCK, FEW_ROWS, grouped_dot

# uniform router's shares of rows the buffer holds.  At 2 the cell's routers,
# drawn from the run's seed, outgrow the buffer in 2 of 14 seeds (counted on
# the CPU), at 2.5 in none of those and in 2 of 20 on the chip (PERF.md, PR
# 40): a further pass costs a whole buffer's work
SHARES = 2.5
EXPERTS_SCOPE = "experts"   # the two products, forward and backward (benchmark/scopes.py)


def choose(scores, bias, top_k: int, scale: float, renormalise: bool = True):
    """scores (n, E) float32 in (0, 1), bias (E,) -> (chosen (n, k) int32:
    the ``top_k`` largest of ``scores + bias``; gates (n, k) float32:
    ``scale`` x the chosen's own scores over their sum).  The bias chooses
    only.  A softmax over the chosen logits is this on ``softmax(logits)``
    with bias 0 and scale 1: the sum over the chosen cancels the rest.
    Gates are renormalised over the chosen where the family does so (the
    ``sigmoid`` and ``softmax`` routers); without ``renormalise`` (the ``mlp``
    router) a gate is ``scale`` x the chosen's own score: at ``top_k`` 1 a
    renormalised gate is 1.0 whatever the scores, and no gradient reaches
    the router."""
    _, chosen = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if not renormalise:
        return chosen.astype(jnp.int32), scale * picked
    return chosen.astype(jnp.int32), scale * picked / picked.sum(axis=-1, keepdims=True)


def _gather_sum(rows, pos, live):
    """out[t] = sum_j live[t, j] * rows[pos[t, j]]."""
    at = jnp.clip(pos, 0, rows.shape[0] - 1)
    out = 0     # choice by choice: (n, d) gathered at a time, never (n, k, d)
    for j in range(pos.shape[1]):
        out = out + jnp.where(live[:, j, None], rows[at[:, j]], 0)
    return out


@jax.custom_vjp
def _dispatch(h, tok, pos, live):
    """rows[r] = h[tok[r]]; its transpose sums a token's rows back."""
    return h[tok]


def _dispatch_fwd(h, tok, pos, live):
    return h[tok], (pos, live)


def _dispatch_bwd(res, d_rows):
    pos, live = res
    return _gather_sum(d_rows, pos, live), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, tok, pos, live):
    """out[t] = sum of token t's rows; its transpose hands each row its
    token's cotangent."""
    return _gather_sum(rows, pos, live)


def _combine_fwd(rows, tok, pos, live):
    return _gather_sum(rows, pos, live), tok


def _combine_bwd(tok, d_out):
    return d_out[tok], None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _weigh(down, gate, used, dtype):
    """weighted[r] = gate[r] x down[r] in ``dtype`` where slot ``r`` is
    ``used``, else 0: a select, since past the live blocks ``down`` is
    uninitialised and a NaN times a gate of 0 is a NaN.  Backward the gate's
    cotangent, a sum over such a row, is selected the same way, and
    ``down``'s is the token's times a gate that is 0 there."""
    return jnp.where(used[:, None], down * gate[:, None], 0.0).astype(dtype)


def _weigh_fwd(down, gate, used, dtype):
    return _weigh(down, gate, used, dtype), (down, gate, used)


def _weigh_bwd(dtype, saved, d_weighted):
    down, gate, used = saved
    d_weighted = d_weighted.astype(down.dtype)
    return (d_weighted * gate[:, None],
            jnp.where(used, (d_weighted * down).sum(axis=-1), 0.0).astype(gate.dtype), None)


_weigh.defvjp(_weigh_fwd, _weigh_bwd)


def _in_kernel(dtype) -> bool:
    """Whether operands of ``dtype`` go through the grouped kernel: bfloat16
    ones; the plain block products run at the caller's matmul precision,
    which a kernel's dots would not see."""
    return dtype == jnp.bfloat16


def block_rows(n: int, top_k: int, experts: int, dtype) -> int:
    """Rows of a block, the rows that share one expert's weights, for ``n``
    tokens of ``dtype``: ``FEW_ROWS`` where a uniform router gives an expert
    fewer than that and the products are the kernel's (bfloat16), else
    ``BLOCK``.  The float32 block products copy a block's weights out
    (``w[owner]``), so more and lower blocks would cost them memory."""
    few = _in_kernel(dtype) and n * top_k < FEW_ROWS * experts
    return FEW_ROWS if few else BLOCK


def row_buffer(n: int, top_k: int, held: int, experts: int, block: int) -> Tuple[int, int]:
    """(blocks of the buffer, passes that cover the worst case) for ``n``
    tokens in blocks of ``block`` rows: ``SHARES`` times a uniform router's
    share of rows and a block of padding for each held expert, and at most
    the worst case (every token choosing ``min(top_k, held)`` held experts)
    with its padding."""
    worst = -(-n * min(top_k, held) // block) + held
    blocks = min(worst, math.ceil(SHARES * n * top_k * held / (experts * block)) + held)
    return blocks, -(-worst // blocks)


def reads_in_place(dtype) -> bool:
    """Whether ``held_mix`` can be handed the stack of a scan's periods and
    the period (its ``period``), not the period's weights: where the products
    are the kernel's, which reads a period where it lies."""
    return _in_kernel(dtype)


def held_mix(h, chosen, gates, valid, w1, w2, offset: int, experts: int, gated: bool = False,
             period=None, sinks=None):
    """The held experts' part of the mix.

    h (n, d) tokens, chosen (n, k) int32 over all ``experts``, gates (n, k)
    float32, valid (n,) bool (a token that is padding is routed nowhere),
    w1 (held, d, w), w2 (held, w, d); ``gated``: w1 (held, d, 2 w), one
    fused input matrix whose halves are a and b.  Returns (out (n, d) in h's
    dtype: sum over a token's chosen experts that are held of gate x
    w2[e] relu(w1[e] h)^2, ``gated`` w2[e] (silu(a) b); counts: ``rows`` (held,) int32 the rows each held
    expert computed, ``passes`` () int32 the passes over the row buffer that
    took them, ``slots`` () int32 the buffer slots of those passes,
    ``blocks_run`` () int32 the slots of them whose blocks the products ran:
    where they skip (``_skips``) the blocks that hold a row, else all).

    ``period`` () int32 with ``sinks`` (two arrays of ``w1``'s and ``w2``'s
    shape and dtype), for a caller that scans over equal periods
    (``reads_in_place``): w1 (periods, held, d, w) and w2 (periods, held, w,
    d) are the periods' stacks, which the scan closes over with no gradient
    path (``stop_gradient``: a stack with one has its cotangent summed whole
    every iteration), and the weights are their period ``period``, read where
    they lie.  The gradient goes through the sinks instead: they ride the
    scan's carry untouched and come back under ``counts["sinks"]``, so their
    cotangent is a carry of the backward scan, the stacked gradient, of
    which this call's backward writes period ``period`` in place (``_passes_at``;
    ``open_sinks`` starts it).  ``counts["in_place"]`` is then 1."""
    n, k = chosen.shape
    held = w1.shape[-3]
    block = block_rows(n, k, experts, h.dtype)
    local = chosen - offset
    live = (local >= 0) & (local < held) & valid[:, None]
    with jax.named_scope("route"):
        key = jnp.where(live, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)    # sorted row -> pair
        rank = jnp.argsort(order).astype(jnp.int32)                # pair -> sorted row
        rows = (key[:, None] == jnp.arange(held)[None, :]).sum(axis=0).astype(jnp.int32)
        first = jnp.cumsum(rows) - rows                            # an expert's first sorted row
        padded = -(-rows // block) * block
        ends = jnp.cumsum(padded)                                  # past an expert's last slot
        base = jnp.append(ends - padded, 0)                        # an expert's first slot
        slot = (base[key] + rank - jnp.append(first, 0)[key]).reshape(n, k)   # pair -> slot
    route = {"order": order, "slot": slot, "live": live, "rows": rows, "first": first,
             "ends": ends, "base": base}
    blocks = row_buffer(n, k, held, experts, block)[0]
    passes = _needed(route, blocks * block)
    if period is None:
        out, more = _passes(h, gates, w1, w2, route, blocks, block, gated), {}
    else:
        assert reads_in_place(h.dtype), h.dtype
        out, sinks = _passes_at(h, gates, w1, w2, route, period, sinks, blocks, block, gated)
        more = {"sinks": sinks, "in_place": jnp.int32(1)}
    # every expert's rows start on a block and the passes cut the slots laid
    # out at whole blocks: the live blocks of all passes are ``ends[-1]`` slots
    slots = passes * (blocks * block)
    return out, {"rows": rows, "passes": passes, "slots": slots,
                 "blocks_run": ends[-1] if _skips(block, h.dtype) else slots, **more}


def _block_products(x, w1, w2, owner, gated: bool = False, into=(None, None), period=None,
                    live=None):
    """x (m, d) in ``owner.size`` blocks of equal height, block ``b`` of
    expert ``owner[b]`` -> (m, d) float32: w2[e] relu(w1[e] x)^2 a block,
    ``gated`` w2[e] (silu(a) b) with [a, b] = w1[e] x.  ``into`` (the kernel's
    products only): ``grouped_dot``'s ``into`` for w1's product and for
    w2's, the sums of their gradients that a loop over passes carries;
    ``period`` (the same only): ``grouped_dot``'s, w1 and w2 stacks; ``live``
    (the same only): ``grouped_dot``'s, the blocks that hold a row: the
    others' rows come back uninitialised (every line between the two products
    is a row's own, so they reach no other row)."""
    if _in_kernel(x.dtype):
        def product(rows, w, into):
            return grouped_dot(rows, w, owner, None, into, period, live)
    else:
        def product(rows, w, _):
            blocks = rows.reshape(owner.size, -1, rows.shape[1])
            return jnp.einsum("brk,bkn->brn", blocks, w[owner],
                              preferred_element_type=jnp.float32).reshape(rows.shape[0], -1)
    up = product(x, w1, into[0])
    if gated:
        a, b = jnp.split(up, 2, axis=-1)
        act = (jax.nn.silu(a) * b).astype(x.dtype)
    else:
        act = jnp.square(jax.nn.relu(up)).astype(x.dtype)
    return product(act, w2, into[1])


def _owners(ends, start, blocks: int, block: int):
    """owner (blocks,) int32, non-decreasing: the expert of each block of the
    buffer slots [start, start + blocks x block).  Every block has one: the
    blocks past the last row are the last expert's, whose weight tile the
    kernels then hold already; they are past ``_one_pass``'s ``live`` and the
    kernels run none of them."""
    owner = jnp.searchsorted(ends, start + block * jnp.arange(blocks), side="right")
    return jnp.minimum(owner, ends.size - 1).astype(jnp.int32)


def _skips(block: int, dtype) -> bool:
    """Whether the products run the blocks that hold a row and no others:
    the kernel's, in blocks of ``BLOCK``.  The plain products multiply every
    block, and so do the kernels in blocks of ``FEW_ROWS``: a rollout step's
    products are bound by the held experts' bytes, and
    ``granite_actor_b32``'s ``rollout_roofline_share`` counts every held
    expert's, at 95% of the roofline with the trailing blocks multiplied; run
    without them the step read over 100% (PERF.md, PR 60), so they stay
    until that count is the read experts'."""
    return _in_kernel(dtype) and block != FEW_ROWS


def _live(ends, start, blocks: int, block: int, dtype):
    """() int32: the blocks of the buffer slots [start, start + blocks x
    block) that the products run: those that hold a row, which are the
    pass's first (every expert's rows start on a block, so the slots laid
    out are the buffer's first ``ends[-1]``; 0 blocks where no token chose a
    held expert or the pass starts past them); every block where the
    products do not skip (``_skips``)."""
    if not _skips(block, dtype):
        return jnp.int32(blocks)
    return jnp.clip(-(-(ends[-1] - start) // block), 0, blocks).astype(jnp.int32)


def _one_pass(h, gates, w1, w2, route, start, blocks: int, block: int, gated: bool = False,
              into=(None, None), period=None):
    """Slots [start, start + blocks x block) of the row buffer ``route`` lays
    out.  ``into``, ``period``: ``_block_products``'."""
    n, k = route["slot"].shape
    m = blocks * block
    rows, first, ends, base = (route[key] for key in ("rows", "first", "ends", "base"))
    with jax.named_scope("route"):
        owner = _owners(ends, start, blocks, block)
        live = _live(ends, start, blocks, block, h.dtype)
        expert = jnp.repeat(owner, block)
        index = start + jnp.arange(m) - base[expert]                # a slot's row of its expert
        used = index < rows[expert]
        pair = route["order"][jnp.clip(first[expert] + index, 0, n * k - 1)]
        tok = pair // k
        at = route["slot"] - start
        in_buffer = route["live"] & (at >= 0) & (at < m)
        x = _dispatch(h, tok, at, in_buffer)
        gate = jnp.where(used, gates.reshape(-1)[pair], 0.0)
    with jax.named_scope(EXPERTS_SCOPE):
        down = _block_products(x, w1, w2, owner, gated, into, period, live)
    with jax.named_scope("route"):
        return _combine(_weigh(down, gate, used, h.dtype), tok, at, in_buffer)


def _needed(route, slots: int):
    """Passes over a buffer of ``slots`` that the rows laid out take: one,
    but for an update whose rows outgrow it."""
    return jnp.maximum(1, -(-route["ends"][-1] // slots))


def _every_pass(h, gates, w1, w2, route, blocks: int, block: int, gated: bool, period=None):
    """The forward loop: one more pass while the rows laid out need one."""
    def one_more(carry):
        done, out = carry
        return done + 1, out + _one_pass(
            h, gates, w1, w2, route, done * blocks * block, blocks, block, gated, period=period)

    return jax.lax.while_loop(
        lambda carry: carry[0] < _needed(route, blocks * block), one_more,
        (jnp.int32(0), jnp.zeros_like(h)))[1]


def _every_pass_back(blocks: int, block: int, gated: bool, saved, d_out, period=None, sums=None):
    """The backward loop -> the cotangents of (h, gates, w1, w2), the last
    two the sums this loop carries: fresh allocations, or with a ``period``
    ``sums``, the stacked gradients handed in, whose period ``period`` the
    passes' kernels write."""
    h, gates, w1, w2, route = saved
    carried = _in_kernel(h.dtype)   # else the plain products' gradients, summed here

    def one_more(carry):
        done, (d_h, d_gates, d_w1, d_w2) = carry
        first = done == 0
        into = ((d_w1, first), (d_w2, first)) if carried else (None, None)
        _, pull = jax.vjp(
            lambda *a: _one_pass(
                *a, route, done * blocks * block, blocks, block, gated, into, period),
            h, gates, w1, w2)
        more_h, more_gates, sum_w1, sum_w2 = pull(d_out)
        if not carried:
            sum_w1, sum_w2 = d_w1 + sum_w1, d_w2 + sum_w2
        return done + 1, (d_h + more_h, d_gates + more_gates, sum_w1, sum_w2)

    # the first pass's kernels write the weights' sums whole, whatever the buffers hold
    fresh = (lambda a: jax.lax.empty(a.shape, a.dtype)) if carried else jnp.zeros_like
    return jax.lax.while_loop(
        lambda carry: carry[0] < _needed(route, blocks * block), one_more,
        (jnp.int32(0), (jnp.zeros_like(h), jnp.zeros_like(gates),
                        *(sums or (fresh(w1), fresh(w2))))))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _passes(h, gates, w1, w2, route, blocks: int, block: int, gated: bool = False):
    """Every pass the rows need, in a loop whose trip count is the update's
    own (``lax.while_loop``, differentiated by hand below): the usual update
    runs one pass and holds one pass's buffers, and the worst case, every
    token on held experts, costs memory for one pass too.  The backward
    loop carries the sums of the passes' gradients; the weights' two, each
    the size of the held experts' weights, are summed by the kernel that
    computes them, in the carry's own buffers, which start as allocations
    that the first pass writes whole (``ops/grouped_product.py``
    ``_weight_sums``): no pass of XLA's over a weight-shaped array is in
    the loop's body or before it.  (The first pass is not taken out of the
    loop: a second copy of the pass's kernels made the step's executable a
    quarter larger and its load 8 s longer, PERF.md, PR 40.)  Both loops are
    ``_passes_at``'s too, which runs them over one period of stacked weights
    and carries the stacked sums from call to call."""
    return _every_pass(h, gates, w1, w2, route, blocks, block, gated)


def _passes_fwd(h, gates, w1, w2, route, blocks, block, gated):
    return _passes(h, gates, w1, w2, route, blocks, block, gated), (h, gates, w1, w2, route)


def _passes_bwd(blocks, block, gated, saved, d_out):
    return (*_every_pass_back(blocks, block, gated, saved, d_out), None)


_passes.defvjp(_passes_fwd, _passes_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _passes_at(h, gates, w1, w2, route, period, sinks, blocks: int, block: int, gated: bool):
    """``_passes`` over period ``period`` of the stacks w1 and w2, which a
    scan over periods closes over: -> (out, ``sinks`` as they came).  The
    backward loop's two weight-shaped carries are the sinks' cotangents, the
    stacked gradients that the backward scan carries from period to period:
    a pass's kernels write period ``period`` of them where it lies (a
    period's first pass its sums alone, whatever the buffer held), so the
    scan's transpose neither copies a period's gradient into a stacked one
    nor fills that with zeros first, and the stacks themselves get none."""
    return _every_pass(h, gates, w1, w2, route, blocks, block, gated, period), sinks


def _passes_at_fwd(h, gates, w1, w2, route, period, sinks, blocks, block, gated):
    return (_passes_at(h, gates, w1, w2, route, period, sinks, blocks, block, gated),
            ((h, gates, w1, w2, route), period))


def _passes_at_bwd(blocks, block, gated, saved, cotangents):
    (saved, period), (d_out, d_sinks) = saved, cotangents
    d_h, d_gates, *sums = _every_pass_back(blocks, block, gated, saved, d_out, period, d_sinks)
    return d_h, d_gates, None, None, None, None, tuple(sums)


_passes_at.defvjp(_passes_at_fwd, _passes_at_bwd)


def open_sinks(out, sinks):
    """``out``, past the last call that was handed ``sinks`` (a scan's final
    carry): their cotangent starts here, and as allocations, not zeros: the
    backward scan's first pass over a period writes that period whole, and it
    comes to every period."""
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), sinks)

    @jax.custom_vjp
    def opened(out, sinks):
        return out

    opened.defvjp(
        lambda out, sinks: (out, None),
        lambda _, d_out: (d_out, jax.tree.map(lambda a: jax.lax.empty(a.shape, a.dtype), like)))
    return opened(out, sinks)
