"""The core of a window part's grouped-query attention (rotation, scores,
mask, softmax, values) as one Pallas kernel, a program a row.

``attention_core(q, k, v, past_k, past_v, before, count, ...)`` computes what
``models/hybrid.py`` ``GroupedQueryAttention``'s window mode computes with
``_rope`` and two einsums, on the arrays as the projections write them:

* ``q`` (n, L, Hq x D), ``k`` and ``v`` (n, L, Hk x D): query head ``h``
  (of kv head ``h // group``) is columns ``h x D`` to ``(h + 1) x D``; no
  transpose is made before or after the call;
* ``past_k`` (already rotated) and ``past_v`` (n, past, Hk x D): the keys and
  values the row's earlier window parts left, ``past`` 0 where there are none;
* ``before`` (n,) int32: the observed steps before this part (the past's
  first ``before`` keys are the row's, at positions 0..before-1); ``count``
  (n,) int32: the part's valid steps, a prefix of its ``L`` slots.  Query
  ``i`` is at position ``before + i``, and so is new key ``i``: it is seen
  where ``i < count``, by the queries at most ``memory_len - 1`` behind it.
  The mask and the rotation's angles are built from the two integers, in
  the kernel: no mask and no angle array is read;
* with ``rope_theta`` queries and new keys are rotated (rotate-half pairing,
  float32, cast back to the operands' dtype) before the scores.

* with ``layer`` (2,) int32, ``[reach, turn]``, the call is *told* what the
  static ``memory_len`` and the rotation otherwise fix: queries see ``reach``
  steps back, and positions are multiplied by ``turn`` (0: every angle is 0 and
  nothing turns; 1: as without it).  One program then serves layers that
  differ in both (a local and a global layer of one scanned period,
  ``models/hybrid.py`` ``periods``): the two numbers are prefetched beside
  ``before`` and ``count``.

Returns ``(out (n, L, Hq x D), keys (n, L, Hk x D))``: the attention output
in the layout the ``o`` projection reads, and the new keys as the state keeps
them (rotated; ``k`` itself without ``rope_theta``).  Scores, softmax and both
products' accumulation are float32, the probabilities are cast to the
operands' dtype before the second product, masked scores are ``NEG_INF``: a
row with no valid key gets the uniform mix of every key's values, as the
einsum lines give it.  Differentiable in q, k, v, past_k and past_v: the
backward kernel recomputes the probabilities from the rotated operands and
returns the new keys' cotangent rotated back.

A row's whole (L, L + past) score tile and every head's operands are held in
VMEM at once (no online softmax, no tiling over keys): ``fits`` says for
which operands that holds, and the caller keeps the einsum lines elsewhere.
Pallas on the TPU, the Pallas interpreter elsewhere (``interpret=None``
picks, as ``ops/grouped_product.py`` does).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .grouped_product import _call
from .ring_attention import NEG_INF      # models/transformer.py's, the same number

# what the backward pass's blocks may take of VMEM, double-buffered (the
# kernels ask for grouped_product's 64 MB scope): q, its cotangent and the
# output's, five (L, Hk x D) blocks of keys and values, bf16.  Both cells'
# forward parts are 96 + 8 keys a row: 6.3 MB at sixteen heads of 128
VMEM_BLOCKS = 32 << 20
# a program a row costs some 0.1 us a head whatever the row holds, the einsum
# lines go with its square: on a v5e at 64 rows of sixteen heads the two met
# near 64 queries (8: 0.18 ms a call against 0.02; 96: 0.25 against 0.44)
ROWS_MIN = 64
# the static choices made in this process: (dtype, L, past, Hq, Hk, D) ->
# {"path", "why", ...}, what ``TrainContext`` writes out as ``model.attention_path``
PATHS: Dict[Tuple, Dict] = {}


def fits(dtype, length: int, past: int, heads: int, kv_heads: int, head_dim: int) -> bool:
    """Whether a window part of these operands runs through the kernel:
    bfloat16 (float32 keeps the einsum lines, as ``grouped_product`` keeps
    ``jnp``), heads of whole 128-lane tiles, at least ``ROWS_MIN`` queries
    a row, they and the past in whole tiles of 8 rows, and every block of
    a row under ``VMEM_BLOCKS``.  From dtype and shape alone; the choice and
    its reason are kept in ``PATHS``."""
    name = jnp.dtype(dtype).name
    blocks = 2 * 2 * head_dim * (3 * heads * length + 5 * kv_heads * (length + past))
    refused = (
        (name != "bfloat16", "operands are %s, not bfloat16" % name),
        (head_dim % 128, "head_dim %d is no multiple of 128" % head_dim),
        (length < ROWS_MIN, "%d queries a row, under %d" % (length, ROWS_MIN)),
        (length % 8 or past % 8,
         "%d queries behind %d keys are not whole tiles of 8 rows" % (length, past)),
        (blocks > VMEM_BLOCKS,
         "a row's blocks take %d bytes of VMEM, over %d" % (blocks, VMEM_BLOCKS)),
    )
    why = next((text for failed, text in refused if failed), "")
    PATHS[(name, length, past, heads, kv_heads, head_dim)] = {
        "path": "einsum" if why else "kernel",
        "why": why or "bfloat16 heads of %d, %d keys a row in VMEM" % (head_dim, length + past),
        "queries": length, "past": past, "heads": heads, "kv_heads": kv_heads,
        "head_dim": head_dim, "dtype": name}
    return not why


def _rotation(before, length: int, freq_ref, turn=None):
    """(cos, signed sin) (L, D) of positions ``before + i`` (times ``turn``
    where the call is told one): ``freq_ref`` (1, D) holds the D / 2 inverse
    frequencies twice, the sine's sign is that of the rotate-half pairing."""
    at = before + jax.lax.broadcasted_iota(jnp.int32, (length, 1), 0)
    if turn is not None:
        at = at * turn
    angle = at.astype(jnp.float32) * freq_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, angle.shape, 1)
    return jnp.cos(angle), jnp.where(lane < angle.shape[1] // 2, -1.0, 1.0) * jnp.sin(angle)


def _rotate(x, rotation, back: bool = False):
    """``x`` (L, D) rotated in float32 (``back``: by the opposite angle,
    the rotation's transpose); unchanged where there is no rotation."""
    from jax.experimental.pallas import tpu as pltpu

    if rotation is None:
        return x
    cos, sin = rotation
    x = x.astype(jnp.float32)
    swapped = pltpu.roll(x, x.shape[1] // 2, 1) * sin
    return x * cos - swapped if back else x * cos + swapped


def _allowed(before, count, length: int, past: int, memory_len: int, keys_first: bool = False):
    """(L, L + past) bool: query ``i`` sees key ``j`` (``keys_first``: the
    same (L + past, L), keys down the rows).  The part's keys come
    first here, the past's after them (a softmax does not mind the order)."""
    shape = (length + past, length) if keys_first else (length, length + past)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if keys_first else 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if keys_first else 1)
    new = j < length
    gap = jnp.where(new, i - j, before + i - (j - length))     # in observed steps
    there = (new & (j < count)) | (~new & (j - length < before))
    return there & (gap >= 0) & (gap < memory_len)


def _weights(q, keys, allowed, keys_first: bool = False):
    """The float32 softmax of one head's masked, scaled scores (L, K)
    (``keys_first``: (K, L), the softmax down the rows)."""
    a, b, over = (keys, q, 0) if keys_first else (q, keys, 1)
    scores = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * q.shape[1] ** -0.5
    scores = jnp.where(allowed, scores, NEG_INF)
    weights = jnp.exp(scores - scores.max(axis=over, keepdims=True))
    return weights / weights.sum(axis=over, keepdims=True)


def _unpack(refs, rotary: bool, past: int, operands: int, told: bool = False):
    """(freq, the ``operands`` per-row inputs, past_k, past_v, the outputs,
    [reach, turn]) of a kernel's refs: the frequencies, the past and what
    the layer is told are there only where the call has them."""
    refs = list(refs)
    layer = refs.pop(0) if told else None      # prefetched, behind ``before`` and ``count``
    freq = refs.pop(0) if rotary else None
    rows, refs = refs[:operands], refs[operands:]
    past_k, past_v = (refs.pop(0), refs.pop(0)) if past else (None, None)
    return freq, rows, past_k, past_v, refs, layer


def _forward_kernel(before_ref, count_ref, *refs, group, head_dim, past, memory_len, rotary,
                    told=False):
    from jax.experimental import pallas as pl

    freq_ref, (q_ref, k_ref, v_ref), pk_ref, pv_ref, outs, layer = _unpack(
        refs, rotary, past, 3, told)
    o_ref, keys_ref = outs if rotary else (outs[0], None)
    row = pl.program_id(0)
    length, D = q_ref.shape[0], head_dim
    reach, turn = (layer[0], layer[1]) if told else (memory_len, None)
    allowed = _allowed(before_ref[row], count_ref[row], length, past, reach)
    rotation = _rotation(before_ref[row], length, freq_ref, turn) if rotary else None
    for g in range(k_ref.shape[1] // D):
        cols = slice(g * D, (g + 1) * D)
        keys, values = k_ref[:, cols], v_ref[:, cols]
        if rotary:
            keys = _rotate(keys, rotation).astype(keys.dtype)
            keys_ref[:, cols] = keys
        if past:
            keys = jnp.concatenate([keys, pk_ref[:, cols]], axis=0)
            values = jnp.concatenate([values, pv_ref[:, cols]], axis=0)
        for h in range(g * group, (g + 1) * group):
            cols = slice(h * D, (h + 1) * D)
            q = _rotate(q_ref[:, cols], rotation).astype(q_ref.dtype)
            weights = _weights(q, keys, allowed).astype(values.dtype)
            o_ref[:, cols] = jnp.dot(
                weights, values, preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _backward_kernel(before_ref, count_ref, *refs, group, head_dim, past, memory_len, rotary,
                     told=False):
    from jax.experimental import pallas as pl

    freq_ref, (q_ref, k_ref, v_ref, do_ref, dkeys_ref), pk_ref, pv_ref, outs, layer = _unpack(
        refs, rotary, past, 5, told)
    dq_ref, dk_ref, dv_ref = outs[:3]
    row = pl.program_id(0)
    length, D = q_ref.shape[0], head_dim
    reach, turn = (layer[0], layer[1]) if told else (memory_len, None)
    # the score tile with the keys down its rows: the softmax's sums and
    # the two cotangents that sum over queries then need no transpose
    allowed = _allowed(before_ref[row], count_ref[row], length, past, reach, keys_first=True)
    rotation = _rotation(before_ref[row], length, freq_ref, turn) if rotary else None
    for g in range(k_ref.shape[1] // D):
        kv = slice(g * D, (g + 1) * D)
        keys, values = k_ref[:, kv], v_ref[:, kv]      # the keys as the forward left them
        if past:
            keys = jnp.concatenate([keys, pk_ref[:, kv]], axis=0)
            values = jnp.concatenate([values, pv_ref[:, kv]], axis=0)
        d_keys = jnp.zeros((length + past, D), jnp.float32)
        d_values = jnp.zeros((length + past, D), jnp.float32)
        for h in range(g * group, (g + 1) * group):
            cols = slice(h * D, (h + 1) * D)
            q = _rotate(q_ref[:, cols], rotation).astype(q_ref.dtype)
            do = do_ref[:, cols]
            weights = _weights(q, keys, allowed, keys_first=True)               # (K, L)
            d_values += jnp.dot(weights.astype(do.dtype), do, preferred_element_type=jnp.float32)
            d_weights = jax.lax.dot_general(values, do, (((1,), (1,)), ((), ())),
                                            preferred_element_type=jnp.float32)
            d_scores = weights * (d_weights - (d_weights * weights).sum(axis=0, keepdims=True))
            # a masked score is a constant: nothing flows through it
            d_scores = (jnp.where(allowed, d_scores, 0.0) * D ** -0.5).astype(q.dtype)
            dq_ref[:, cols] = _rotate(
                jax.lax.dot_general(d_scores, keys, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32),
                rotation, back=True).astype(dq_ref.dtype)
            d_keys += jnp.dot(d_scores, q, preferred_element_type=jnp.float32)
        d_new = d_keys[:length] + dkeys_ref[:, kv].astype(jnp.float32)
        dk_ref[:, kv] = _rotate(d_new, rotation, back=True).astype(dk_ref.dtype)
        dv_ref[:, kv] = d_values[:length].astype(dv_ref.dtype)
        if past:
            outs[3][:, kv] = d_keys[length:].astype(outs[3].dtype)
            outs[4][:, kv] = d_values[length:].astype(outs[4].dtype)


def _run(kernel, rows, past_k, past_v, outs, before, count, static, interpret, layer=None):
    """One program a row over ``rows`` (each (n, L, columns)) and the past,
    where there is one: -> arrays of the ``outs`` shapes.  ``static`` is
    (group, head_dim, memory_len, rope_theta); ``layer`` the module
    docstring's."""
    from jax.experimental import pallas as pl

    group, D, memory_len, rope_theta = static
    whole = lambda x: pl.BlockSpec((None,) + x.shape[1:], lambda r, *_: (r, 0, 0))  # noqa: E731
    operands = list(rows) + ([past_k, past_v] if past_k.shape[1] else [])
    specs = [whole(x) for x in operands]
    if rope_theta:      # as models/hybrid.py _rope has them, once for each half
        freq = rope_theta ** (-jnp.arange(D // 2, dtype=jnp.float32) / (D // 2))
        operands.insert(0, jnp.tile(freq, 2)[None])
        specs.insert(0, pl.BlockSpec((1, D), lambda r, *_: (0, 0)))
    return _call(
        functools.partial(kernel, group=group, head_dim=D, past=past_k.shape[1],
                          memory_len=memory_len, rotary=bool(rope_theta), told=layer is not None),
        (before, count) + (() if layer is None else (layer,)), (rows[0].shape[0],), specs,
        [whole(x) for x in outs], outs, [], interpret, *operands)


@functools.partial(jax.jit, static_argnames=("static", "interpret"))
def _forward(q, k, v, past_k, past_v, before, count, static, interpret, layer=None):
    """(out, keys): jitted, as ``grouped_product``'s callees are (a net calls
    it at two shapes, a layer, a pass and a replay at a time)."""
    outs = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if static[3]:
        outs.append(jax.ShapeDtypeStruct(k.shape, k.dtype))
    got = _run(_forward_kernel, (q, k, v), past_k, past_v, outs, before, count, static, interpret,
               layer)
    return got[0], (got[1] if static[3] else k)


@functools.partial(jax.jit, static_argnames=("static", "interpret"))
def _backward(q, keys, v, past_k, past_v, before, count, d_out, d_keys, static, interpret,
              layer=None):
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype)
            for x in (q, keys, v) + ((past_k, past_v) if past_k.shape[1] else ())]
    got = _run(_backward_kernel, (q, keys, v, d_out, d_keys), past_k, past_v, outs, before,
               count, static, interpret, layer)
    return tuple(got) if past_k.shape[1] else tuple(got) + (past_k, past_v)   # nothing: (n, 0, ..)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def attention_core(q, k, v, past_k, past_v, before, count, static,
                   interpret: Optional[bool] = None, layer=None):
    """See the module's docstring; ``static`` is (group = Hq / Hk, head_dim,
    memory_len, rope_theta (0: no rotation)), ``layer`` None or ``[reach,
    turn]`` in the place of the static ``memory_len`` and of a rotation that
    always turns.  -> (out, keys)."""
    return _core_fwd(q, k, v, past_k, past_v, before, count, static, interpret, layer)[0]


def _core_fwd(q, k, v, past_k, past_v, before, count, static, interpret, layer):
    if interpret is None:   # picked before the jitted callee, whose cache it keys
        interpret = jax.default_backend() != "tpu"
    out, keys = _forward(q, k, v, past_k, past_v, before, count, static, interpret, layer)
    return (out, keys), (q, keys, v, past_k, past_v, before, count, layer)


def _core_bwd(static, interpret, saved, cotangents):
    q, keys, v, past_k, past_v, before, count, layer = saved
    d_out, d_keys = cotangents
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _backward(q, keys, v, past_k, past_v, before, count, d_out.astype(q.dtype),
                     d_keys.astype(keys.dtype), static, interpret, layer) + (None, None, None)


attention_core.defvjp(_core_fwd, _core_bwd)
