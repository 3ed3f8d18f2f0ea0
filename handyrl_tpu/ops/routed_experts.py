"""A routed expert layer's two halves: the router's choice, and the part of
the mix that the experts held here give.

The layer is told which experts it holds (``w1``/``w2`` carry ``held`` of
them, ``offset`` is the first one's index among all).  It scores and chooses
over all experts, normalises the gates over all the chosen, and adds only
its own experts' terms: what expert parallelism asks of one chip.  On one
chip it runs without the exchange; nothing stands in for the absent chips.

No token is dropped at any imbalance.  The (token, choice) pairs that fall
on held experts are sorted by expert into a row buffer in which every
expert's rows start on a block of ``BLOCK`` rows, so a block belongs to one
expert: the two products are batched matrix products over the blocks, each
with its expert's weights (picked by a one-hot product, whose transpose sums
the blocks' weight gradients back per expert).  Shapes are static and so is
the work: the buffer holds a uniform router's share of rows and a block of
padding for each held expert, and every block is computed whether rows fill
it or not (the step's time does not move with the routing; what the chip
showed of a grouped kernel that skips empty tiles is in PERF.md, PR 34).
An update whose rows outgrow the buffer takes further passes over it, as
many as its rows need (``_passes``: a ``lax.while_loop`` forward and
backward, so the worst case, every token choosing ``min(top_k, held)`` held
experts, costs time and no memory).  Filling the buffer and summing a
token's rows back are each other's transpose and are written as gathers
both ways (``_dispatch`` / ``_combine``), so no scatter runs forward or
backward.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

BLOCK = 512         # rows of the buffer that share one expert's weights
AT_ONCE = 6         # blocks whose weights are copied out together
_EXACT = jax.lax.Precision.HIGHEST     # picking weights must not round them


def choose(scores, bias, top_k: int, scale: float):
    """scores (n, E) float32 in (0, 1), bias (E,) -> (chosen (n, k) int32:
    the ``top_k`` largest of ``scores + bias``; gates (n, k) float32:
    ``scale`` x the chosen's own scores over their sum).  The bias chooses
    only."""
    _, chosen = jax.lax.top_k(scores + bias.astype(scores.dtype), top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
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


def row_buffer(n: int, top_k: int, held: int, experts: int) -> Tuple[int, int]:
    """(blocks of the buffer, passes that cover the worst case) for ``n``
    tokens: a uniform router's share of rows and a block of padding for each
    held expert, and at most the worst case (every token choosing
    ``min(top_k, held)`` held experts) with its padding."""
    worst = -(-n * min(top_k, held) // BLOCK) + held
    blocks = min(worst, -(-n * top_k * held // (experts * BLOCK)) + held)
    blocks = -(-blocks // AT_ONCE) * AT_ONCE
    return blocks, -(-worst // blocks)


def held_mix(h, chosen, gates, valid, w1, w2, offset: int, experts: int):
    """The held experts' part of the mix.

    h (n, d) tokens, chosen (n, k) int32 over all ``experts``, gates (n, k)
    float32, valid (n,) bool (a token that is padding is routed nowhere),
    w1 (held, d, w), w2 (held, w, d).  Returns (out (n, d) in h's dtype:
    sum over a token's chosen experts that are held of gate x
    w2[e] relu(w1[e] h)^2; rows (held,) int32: the rows each held expert
    computed)."""
    n, k = chosen.shape
    held = w1.shape[0]
    local = chosen - offset
    live = (local >= 0) & (local < held) & valid[:, None]
    with jax.named_scope("route"):
        key = jnp.where(live, local, held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)    # sorted row -> pair
        rank = jnp.argsort(order).astype(jnp.int32)                # pair -> sorted row
        rows = (key[:, None] == jnp.arange(held)[None, :]).sum(axis=0).astype(jnp.int32)
        first = jnp.cumsum(rows) - rows                            # an expert's first sorted row
        padded = -(-rows // BLOCK) * BLOCK
        ends = jnp.cumsum(padded)                                  # past an expert's last slot
        base = jnp.append(ends - padded, 0)                        # an expert's first slot
        slot = (base[key] + rank - jnp.append(first, 0)[key]).reshape(n, k)   # pair -> slot
    route = {"order": order, "slot": slot, "live": live, "rows": rows, "first": first,
             "ends": ends, "base": base}
    return _passes(h, gates, w1, w2, route, row_buffer(n, k, held, experts)[0]), rows


def _one_pass(h, gates, w1, w2, route, start, blocks: int):
    """Slots [start, start + blocks x BLOCK) of the row buffer ``route`` lays out."""
    n, k = route["slot"].shape
    held, m = w1.shape[0], blocks * BLOCK
    rows, first, ends, base = (route[key] for key in ("rows", "first", "ends", "base"))
    with jax.named_scope("route"):
        owner = jnp.searchsorted(ends, start + BLOCK * jnp.arange(blocks), side="right")
        picks = (owner[:, None] == jnp.arange(held)[None, :]).astype(h.dtype)
        expert = jnp.repeat(jnp.minimum(owner, held - 1), BLOCK)
        index = start + jnp.arange(m) - base[expert]                # a slot's row of its expert
        used = (jnp.repeat(owner, BLOCK) < held) & (index < rows[expert])
        pair = route["order"][jnp.clip(first[expert] + index, 0, n * k - 1)]
        tok = pair // k
        at = route["slot"] - start
        in_buffer = route["live"] & (at >= 0) & (at < m)
        x = _dispatch(h, tok, at, in_buffer).reshape(blocks, BLOCK, -1)
        gate = jnp.where(used, gates.reshape(-1)[pair], 0.0)

    def some_blocks(group):
        x, picks = group
        up = jnp.einsum("brd,bdw->brw", x, jnp.einsum("be,edw->bdw", picks, w1, precision=_EXACT),
                        preferred_element_type=jnp.float32)
        act = jnp.square(jax.nn.relu(up)).astype(h.dtype)
        return jnp.einsum("brw,bwd->brd", act, jnp.einsum("be,ewd->bwd", picks, w2, precision=_EXACT),
                          preferred_element_type=jnp.float32)

    with jax.named_scope("experts"):
        # a few blocks at a time: their experts' weights are copied out per
        # block, and so are those copies' gradients
        split = lambda a: a.reshape((blocks // AT_ONCE, AT_ONCE) + a.shape[1:])  # noqa: E731
        down = jax.lax.map(some_blocks, (split(x), split(picks)))
    with jax.named_scope("route"):
        # a slot no row fills has gate 0
        weighted = (down.reshape(m, -1) * gate[:, None]).astype(h.dtype)
        return _combine(weighted, tok, at, in_buffer)


def _needed(route, blocks: int):
    """Passes over the buffer that the rows laid out take: one, but for an
    update whose rows outgrow it."""
    return jnp.maximum(1, -(-route["ends"][-1] // (blocks * BLOCK)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _passes(h, gates, w1, w2, route, blocks: int):
    """Every pass the rows need, in a loop whose trip count is the update's
    own (``lax.while_loop``, differentiated by hand below): the usual update
    runs one pass and holds one pass's buffers, and the worst case, every
    token on held experts, costs memory for one pass too."""
    def one_more(carry):
        done, out = carry
        return done + 1, out + _one_pass(h, gates, w1, w2, route, done * blocks * BLOCK, blocks)

    return jax.lax.while_loop(
        lambda carry: carry[0] < _needed(route, blocks), one_more,
        (jnp.int32(0), jnp.zeros_like(h)))[1]


def _passes_fwd(h, gates, w1, w2, route, blocks):
    return _passes(h, gates, w1, w2, route, blocks), (h, gates, w1, w2, route)


def _passes_bwd(blocks, saved, d_out):
    h, gates, w1, w2, route = saved

    def one_more(carry):
        done, sums = carry
        _, pull = jax.vjp(
            lambda *a: _one_pass(*a, route, done * blocks * BLOCK, blocks), h, gates, w1, w2)
        return done + 1, jax.tree.map(jnp.add, sums, pull(d_out))

    zeros = jax.tree.map(jnp.zeros_like, (h, gates, w1, w2))
    sums = jax.lax.while_loop(
        lambda carry: carry[0] < _needed(route, blocks), one_more, (jnp.int32(0), zeros))[1]
    return (*sums, None)


_passes.defvjp(_passes_fwd, _passes_bwd)
