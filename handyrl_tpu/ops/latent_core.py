"""The core of a window part's multi-head latent attention (scores as two
products summed, mask, softmax, mix) as one Pallas kernel, a program a row.

``latent_core(q, kv, kr, before, count, static)`` computes what
``models/hybrid.py`` ``LatentAttention``'s window mode computes with three
einsums, on the arrays as the projections write them:

* ``q`` (n, L, H x (Dn + Dr)) as its product writes it: head ``h`` is
  columns ``h x (Dn + Dr)`` on, ``Dn`` dimensions that keep no position and
  then ``Dr`` that the kernel turns by the query's position (adjacent
  pairing, float32, cast back: ``models/hybrid.py`` ``_rope_pairs``), the
  angles built from ``before`` as the mask is.  No reshaped, rotated or
  re-laid copy of ``q`` stands before the call, and none of its cotangent,
  which the backward kernel turns back, behind it;
* ``kv`` (n, K, H x (Dn + Dv)) as ``latents @ kv_b`` writes it: a head's
  key part, then its value; ``kr`` (n, K, Dr): the key part the caller rotated (what
  the state keeps), one for every head.  The ``K = L + past`` keys are the part's own ``L`` and then
  the ``past`` ones the row's earlier parts left (``attention_core``'s
  order, whose mask this kernel builds; a softmax does not mind);
* ``before``, ``count`` (n,) int32 as ``attention_core``'s: query ``i`` is
  at position ``before + i``, own key ``i`` is seen where ``i < count``, the
  past's first ``before`` keys are the row's.  No mask is read.

Returns ``out`` (n, L, H x Dv), the layout the ``o`` projection reads.  No
array per (row, head, query, key) and no transposed copy of an operand is
made before, in or after the call.  Scores ``(qn . kn + qr . kr) x (Dn +
Dr) ** -0.5`` accumulate both products in float32, masked scores are
``NEG_INF``, the softmax is float32, the probabilities are cast to the
operands' dtype before the mix, which accumulates in float32: a row with no
valid key gets the uniform mix of every key's values, as the einsum lines
give it.  Differentiable in q, kv and kr: the backward kernel recomputes the
probabilities and sums ``kr``'s cotangent over the heads in float32, written
once a row.

A row's every head and the whole (L, K) score tile of one are in VMEM at
once (no online softmax, no tiling over keys); ``fits`` says for which
operands that holds, and the caller keeps the einsum lines elsewhere.  The
heads run as a ``lax.fori_loop`` over lane-aligned column blocks, so the
kernels' bodies are traced for one block whatever ``heads`` is: with ``Dr``
64 a block is two heads (an odd head's columns begin half a tile in), the
second one's queries brought to a tile's edge by one lane rotation.  Pallas
on the TPU, the Pallas interpreter elsewhere (``interpret=None`` picks).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .attention_core import PATHS, ROWS_MIN, VMEM_BLOCKS, _allowed
from .grouped_product import _call
from .ring_attention import NEG_INF

LANES = 128
# column blocks unrolled inside a step of the heads' loop: the body is traced once
# whatever this is, and lowered this many times.  On a v5e at the cell's shapes a
# forward call took 1.04 ms at 1, 0.78 at 2, 0.72 at 4 and the backward 1.33, 1.18,
# 1.05 (0.64 forward with the rotation's swap as a product); 16 gave 0.62 and 1.06
# for 9 s of Mosaic compile where 4 takes 3 (PERF.md, PR 56)
UNROLL = 4
KIND = "L"      # what the choices' keys and events are named by in ``attention_core.PATHS``


def fits(dtype, length: int, past: int, heads: int, qk_nope: int, qk_rope: int,
         v_head: int) -> bool:
    """Whether a window part of these operands runs through the kernel:
    bfloat16 (float32 keeps the einsum lines), ``qk_nope`` and ``v_head``
    whole 128-lane tiles, ``qk_rope`` 64 (an even number of heads then) or
    128, at least ``ROWS_MIN`` queries a row, they and the past in whole
    tiles of 8 rows, and the backward pass's blocks of a row (q, kv and
    their cotangents, the output's) double-buffered under ``VMEM_BLOCKS``.
    From dtype and shape alone; the choice and its reason are kept in
    ``attention_core.PATHS`` under a key that begins with ``KIND``."""
    name = jnp.dtype(dtype).name
    keys = length + past
    blocks = 2 * 2 * (heads * (2 * length * (qk_nope + qk_rope) + 2 * keys * (qk_nope + v_head)
                               + length * v_head) + 2 * keys * LANES)
    refused = (
        (name != "bfloat16", "operands are %s, not bfloat16" % name),
        (qk_nope % LANES or v_head % LANES,
         "qk_nope %d and v_head %d are not whole tiles of 128 lanes" % (qk_nope, v_head)),
        (qk_rope not in (64, LANES), "qk_rope %d is neither 64 nor 128" % qk_rope),
        (heads * qk_rope % LANES, "%d heads of qk_rope %d end inside a tile" % (heads, qk_rope)),
        (length < ROWS_MIN, "%d queries a row, under %d" % (length, ROWS_MIN)),
        (length % 8 or past % 8,
         "%d queries behind %d keys are not whole tiles of 8 rows" % (length, past)),
        (blocks > VMEM_BLOCKS,
         "a row's blocks take %d bytes of VMEM, over %d" % (blocks, VMEM_BLOCKS)),
    )
    why = next((text for failed, text in refused if failed), "")
    PATHS[(KIND, name, length, past, heads, qk_nope, qk_rope, v_head)] = {
        "path": "einsum" if why else "kernel",
        "why": why or "bfloat16 heads of %d + %d against %d, %d keys a row in VMEM" % (
            qk_nope, qk_rope, v_head, keys),
        "kind": KIND, "queries": length, "past": past, "heads": heads, "qk_nope": qk_nope,
        "qk_rope": qk_rope, "v_head": v_head, "dtype": name}
    return not why


def _columns(start, width: int):
    """``width`` columns of a ref from ``start`` on, a traced multiple of 128."""
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(start, LANES), width)


def _rotation(before, length: int, freq_ref, swap_dtype=None):
    """(cos, signed sin (L, 128), swap) of positions ``before + i``:
    ``freq_ref`` (1, 128) holds each pair's inverse frequency twice and zeros
    behind ``Dr`` (no turn there), the sine's sign is that of the adjacent
    pairing.  ``swap``, where a dtype is given for it, is the 0/1 matrix
    (128, 128) that exchanges lane 2j with lane 2j + 1: a product with it is
    exact, and in the forward kernel, whose MXU is idle, cheaper than two
    lane rotations and a select (0.64 ms a call against 0.72); the backward
    kernel's seven products a head leave it no room (1.20 against 1.05)."""
    at = (before + jax.lax.broadcasted_iota(jnp.int32, (length, 1), 0)).astype(jnp.float32)
    angle = at * freq_ref[...]
    sign = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) % 2 == 0, -1.0, 1.0)
    swap = None
    if swap_dtype is not None:
        lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
        other = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        swap = (other + 1 - 2 * (other % 2) == lane).astype(swap_dtype)
    return jnp.cos(angle), sign * jnp.sin(angle), swap


def _rotate(x, rotation, back: bool = False):
    """A tile ``x`` (L, 128) rotated in float32, lane 2j with lane 2j + 1
    (``back``: by the opposite angle, the rotation's transpose)."""
    from jax.experimental.pallas import tpu as pltpu

    cos, sin, swap = rotation
    wide = x.astype(jnp.float32)
    if swap is not None:
        other = jnp.dot(x, swap, preferred_element_type=jnp.float32)
    else:
        even = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) % 2 == 0
        other = jnp.where(even, pltpu.roll(wide, LANES - 1, 1), pltpu.roll(wide, 1, 1))
    return wide * cos - other * sin if back else wide * cos + other * sin


def _queries(q_ref, block, Dn: int, together: int, rotation):
    """[(qn (L, Dn), qr (L, 128))] of the ``together`` heads of column block
    ``block``: ``qr``'s first ``Dr`` lanes are the head's rotated part, and
    what lies behind them where ``Dr`` is 64 meets zeros in ``kr``'s tile.
    Two heads of 64 are ``[qn0 | qr0 qn1 | qr1]``: the second's are that,
    from ``qr0`` on, turned 64 lanes to the left."""
    from jax.experimental.pallas import tpu as pltpu

    start = block * (together * Dn + LANES)     # a block's heads' rotated parts are one tile
    turn = lambda tile: _rotate(tile, rotation).astype(tile.dtype)      # noqa: E731
    first = (q_ref[:, _columns(start, Dn)], turn(q_ref[:, _columns(start + Dn, LANES)]))
    if together == 1:
        return [first]
    tail = q_ref[:, _columns(start + Dn, Dn + LANES)]
    # [qn1 | qr1 qr0]; lanes are moved 32 bits wide, and bfloat16 goes there and back as it is
    turned = pltpu.roll(tail.astype(jnp.float32), Dn + LANES - 64, 1).astype(tail.dtype)
    return [first, (turned[:, :Dn], turn(turned[:, Dn:]))]


def _put_queries(dq_ref, block, Dn: int, grads, rotation):
    """Write the float32 cotangents [(d_qn (L, Dn), d_qr (L, 128), zeros
    behind ``Dr``)] of a column block's heads where ``_queries`` read them,
    the rotated parts' turned back (from the operands' dtype, as the
    cotangent of a rotation that was cast to it is)."""
    from jax.experimental.pallas import tpu as pltpu

    together = len(grads)
    grads = [(d_qn, _rotate(d_qr.astype(dq_ref.dtype), rotation, back=True))
             for d_qn, d_qr in grads]
    start = block * (together * Dn + LANES)     # a block's heads' rotated parts are one tile
    dq_ref[:, _columns(start, Dn)] = grads[0][0].astype(dq_ref.dtype)
    if together == 1:
        dq_ref[:, _columns(start + Dn, LANES)] = grads[0][1].astype(dq_ref.dtype)
        return
    # [d_qn1 | d_qr1 0] turned 64 lanes to the right: [0 d_qn1 | d_qr1]
    tail = pltpu.roll(jnp.concatenate(grads[1], axis=1), 64, 1)
    dq_ref[:, _columns(start + Dn, LANES)] = (tail[:, :LANES] + grads[0][1]).astype(dq_ref.dtype)
    dq_ref[:, _columns(start + Dn + LANES, Dn)] = tail[:, LANES:].astype(dq_ref.dtype)


def _weights(qn, qr, kn, kr, allowed, scale: float, keys_first: bool = False):
    """The float32 softmax of one head's masked, scaled scores (L, K), two
    products summed (``keys_first``: (K, L), the softmax down the rows)."""
    over = 0 if keys_first else 1
    dims = (((1,), (1,)), ((), ()))
    pairs = ((kn, qn), (kr, qr)) if keys_first else ((qn, kn), (qr, kr))
    scores = sum(jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
                 for a, b in pairs) * scale
    scores = jnp.where(allowed, scores, NEG_INF)
    weights = jnp.exp(scores - scores.max(axis=over, keepdims=True))
    return weights / weights.sum(axis=over, keepdims=True)


def _loop(blocks: int, body, carry):
    """``body(block, carry)`` over the column blocks, ``UNROLL`` of them
    (or the most that divides them) unrolled inside a step of the loop: an
    inner loop lowered ``unroll=True`` (Pallas takes 1 or all), so the body
    is traced once whatever the heads and the unrolling."""
    some = math.gcd(blocks, UNROLL)
    return jax.lax.fori_loop(0, blocks // some, lambda i, carry: jax.lax.fori_loop(
        0, some, lambda j, carry: body(i * some + j, carry), carry, unroll=True), carry)


def _forward_kernel(before_ref, count_ref, freq_ref, q_ref, kv_ref, kr_ref, o_ref, *, widths,
                    past, memory_len):
    from jax.experimental import pallas as pl

    Dn, Dr, Dv = widths
    row, length, together = pl.program_id(0), q_ref.shape[0], LANES // Dr
    allowed = _allowed(before_ref[row], count_ref[row], length, past, memory_len)
    rotation = _rotation(before_ref[row], length, freq_ref, swap_dtype=q_ref.dtype)
    kr = kr_ref[...]

    def heads(block, _):
        for at, (qn, qr) in enumerate(_queries(q_ref, block, Dn, together, rotation)):
            head = block * together + at
            kn = kv_ref[:, _columns(head * (Dn + Dv), Dn)]
            values = kv_ref[:, _columns(head * (Dn + Dv) + Dn, Dv)]
            weights = _weights(qn, qr, kn, kr, allowed, (Dn + Dr) ** -0.5).astype(values.dtype)
            o_ref[:, _columns(head * Dv, Dv)] = jnp.dot(
                weights, values, preferred_element_type=jnp.float32).astype(o_ref.dtype)

    _loop(kv_ref.shape[1] // (together * (Dn + Dv)), heads, None)


def _backward_kernel(before_ref, count_ref, freq_ref, q_ref, kv_ref, kr_ref, do_ref, dq_ref,
                     dkv_ref, dkr_ref, *, widths, past, memory_len):
    from jax.experimental import pallas as pl

    Dn, Dr, Dv = widths
    row, length, together = pl.program_id(0), q_ref.shape[0], LANES // Dr
    # the score tile with the keys down its rows, as ``attention_core``'s
    # backward has it: the cotangents that sum over queries need no transpose
    allowed = _allowed(before_ref[row], count_ref[row], length, past, memory_len, keys_first=True)
    rotation = _rotation(before_ref[row], length, freq_ref)
    kr = kr_ref[...]
    over_keys = (((0,), (0,)), ((), ()))

    def heads(block, d_kr):
        grads = []
        for at, (qn, qr) in enumerate(_queries(q_ref, block, Dn, together, rotation)):
            head = block * together + at
            key_cols = _columns(head * (Dn + Dv), Dn)
            value_cols = _columns(head * (Dn + Dv) + Dn, Dv)
            kn, values, do = kv_ref[:, key_cols], kv_ref[:, value_cols], do_ref[:, _columns(
                head * Dv, Dv)]
            weights = _weights(qn, qr, kn, kr, allowed, (Dn + Dr) ** -0.5, keys_first=True)
            dkv_ref[:, value_cols] = jnp.dot(
                weights.astype(do.dtype), do, preferred_element_type=jnp.float32
            ).astype(dkv_ref.dtype)
            d_weights = jax.lax.dot_general(values, do, (((1,), (1,)), ((), ())),
                                            preferred_element_type=jnp.float32)
            d_scores = weights * (d_weights - (d_weights * weights).sum(axis=0, keepdims=True))
            # a masked score is a constant: nothing flows through it
            d_scores = (jnp.where(allowed, d_scores, 0.0) * (Dn + Dr) ** -0.5).astype(qn.dtype)
            grads.append(tuple(
                jax.lax.dot_general(d_scores, keys, over_keys, preferred_element_type=jnp.float32)
                for keys in (kn, kr)))
            dkv_ref[:, key_cols] = jnp.dot(
                d_scores, qn, preferred_element_type=jnp.float32).astype(dkv_ref.dtype)
            d_kr = d_kr + jnp.dot(d_scores, qr, preferred_element_type=jnp.float32)
        _put_queries(dq_ref, block, Dn, grads, rotation)
        return d_kr

    d_kr = _loop(kv_ref.shape[1] // (together * (Dn + Dv)), heads, jnp.zeros(kr.shape, jnp.float32))
    dkr_ref[...] = d_kr.astype(dkr_ref.dtype)


def _run(kernel, rows, outs, before, count, static, interpret):
    """One program a row over ``rows`` (each (n, ., columns), ``kr`` among
    them padded to a tile of 128 lanes) -> arrays of the ``outs`` shapes."""
    from jax.experimental import pallas as pl

    Dn, Dr, Dv, memory_len, rope_theta = static
    whole = lambda x: pl.BlockSpec((None,) + x.shape[1:], lambda r, *_: (r, 0, 0))  # noqa: E731
    # as models/hybrid.py _turns has them, once for each lane of a pair
    freq = jnp.repeat(rope_theta ** (-jnp.arange(Dr // 2, dtype=jnp.float32) / (Dr // 2)), 2)
    return _call(
        functools.partial(kernel, widths=(Dn, Dr, Dv), past=rows[1].shape[1] - rows[0].shape[1],
                          memory_len=memory_len),
        (before, count), (rows[0].shape[0],),
        [pl.BlockSpec((1, LANES), lambda r, *_: (0, 0))] + [whole(x) for x in rows],
        [whole(x) for x in outs], outs, [], interpret, jnp.pad(freq, (0, LANES - Dr))[None], *rows)


def _tile(kr):
    """``kr`` (n, K, Dr) with zeros behind it up to a tile of 128 lanes."""
    return jnp.pad(kr, ((0, 0), (0, 0), (0, LANES - kr.shape[2])))


@functools.partial(jax.jit, static_argnames=("static", "interpret"))
def _forward(q, kv, kr, before, count, static, interpret):
    """out: jitted, as ``attention_core``'s callees are (the net calls it at
    one shape, a layer, a period of the scan and a replay at a time: they
    share one traced and one lowered function)."""
    heads = q.shape[2] // (static[0] + static[1])
    out = jax.ShapeDtypeStruct(q.shape[:2] + (heads * static[2],), q.dtype)
    return _run(_forward_kernel, (q, kv, _tile(kr)), [out], before, count, static, interpret)[0]


@functools.partial(jax.jit, static_argnames=("static", "interpret"))
def _backward(q, kv, kr, before, count, d_out, static, interpret):
    tile = _tile(kr)
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, kv, tile)]
    d_q, d_kv, d_kr = _run(_backward_kernel, (q, kv, tile, d_out), outs, before, count, static,
                           interpret)
    return d_q, d_kv, d_kr[..., :kr.shape[2]]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def latent_core(q, kv, kr, before, count, static, interpret: Optional[bool] = None):
    """See the module's docstring; ``static`` is (qk_nope, qk_rope, v_head,
    memory_len, rope_theta).  -> out (n, L, heads x v_head)."""
    return _core_fwd(q, kv, kr, before, count, static, interpret)[0]


def _core_fwd(q, kv, kr, before, count, static, interpret):
    if interpret is None:   # picked before the jitted callee, whose cache it keys
        interpret = jax.default_backend() != "tpu"
    return _forward(q, kv, kr, before, count, static, interpret), (q, kv, kr, before, count)


def _core_bwd(static, interpret, saved, d_out):
    q, kv, kr, before, count = saved
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _backward(q, kv, kr, before, count, d_out.astype(q.dtype), static,
                     interpret) + (None, None)


latent_core.defvjp(_core_fwd, _core_bwd)
