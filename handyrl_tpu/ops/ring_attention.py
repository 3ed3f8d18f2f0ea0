"""Ring attention: sequence-parallel exact attention over an ``sp`` mesh axis.

Long-context support beyond the reference (which scales sequence length
*down* via windows + burn-in, SURVEY.md §5.7; train.py:93-107): here the
time axis shards across devices and exact attention is computed blockwise
— each device holds its Q shard, while K/V shards rotate around the ring
via ``ppermute`` (one ICI hop per step), merged with a streaming
(flash-style) softmax.  Memory per device is O(T/n) and the K/V transfer
overlaps compute, so context length scales linearly with the mesh's
``sp`` size.

Layout: ``(B, T, H, D)`` — batch, time, heads, head dim.  Works standalone
under ``shard_map`` (``ring_attention_shard``) or through the convenience
wrapper ``ring_self_attention`` which builds the shard_map over a mesh
with ``sp`` (and optionally ``dp``) axes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attention(q, k, v, q_off, k_off, scale, causal):
    """One Q-shard x K/V-block attention with running-softmax stats.

    q: (B, Tq, H, D); k, v: (B, Tk, H, D).
    Returns (o, m, l): unnormalized output (B, Tq, H, D), row max (B, H, Tq),
    row sum (B, H, Tq).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])
        kpos = k_off + jnp.arange(k.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = s.max(axis=-1)                                   # (B, H, Tq)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)                                   # (B, H, Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o, m, l


def _ring_loop(q, k, v, extras, axis_name: str, scores_fn, vary_axes=()):
    """Shared ring mechanics: each participant holds contiguous time
    shards of equal length (shard i owns positions [i*T_loc, (i+1)*T_loc));
    K/V (and any ``extras`` keyed to the K shard) rotate to the next device
    every step via ppermute, so after n steps every Q shard has seen every
    K/V shard; blocks merge through a streaming (flash-style) softmax.

    ``scores_fn(qf, kf, extras, q0, k0) -> (B, H, Tq, Tk)`` builds the
    (masked/biased) scores for one block — the only part that differs
    between the causal and the production masked semantics.
    ``vary_axes`` lists any additional manual mesh axes in scope (e.g. a
    'dp' batch axis) so the accumulators carry the right varying type.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, T_loc, H, D = q.shape
    qf = q.astype(jnp.float32)

    # accumulators start replicated but become device-varying inside the
    # ring loop; marking them keeps shard_map's VMA typing happy with the
    # carry (golden-pinned against the einsum references by
    # tests/test_parallel.py)
    vary = (axis_name,) + tuple(a for a in vary_axes if a)
    _mark = lambda x: jax.lax.pcast(x, vary, to="varying")
    o = _mark(jnp.zeros((B, T_loc, H, D), jnp.float32))
    m = _mark(jnp.full((B, H, T_loc), NEG_INF, jnp.float32))
    l = _mark(jnp.zeros((B, H, T_loc), jnp.float32))
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        o, m, l, k, v, extras = carry
        k_idx = (idx - i) % n  # owner of the K/V block currently held
        s = scores_fn(qf, k.astype(jnp.float32), extras, idx * T_loc, k_idx * T_loc)
        m_blk = s.max(axis=-1)                           # (B, H, Tq)
        p = jnp.exp(s - m_blk[..., None])
        l_blk = p.sum(axis=-1)
        o_blk = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

        # NOTE on fully-invalid blocks (every score NEG_INF): m_blk is
        # NEG_INF and p collapses to exp(0)=1 garbage, but ring step 0
        # processes the query's OWN shard where self-visibility (causal
        # diagonal / the masked 'self always visible' rule) guarantees a
        # finite m — so for every later all-invalid block beta is
        # exp(NEG_INF - finite) = 0 and the garbage never lands.
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)                       # rescale old accum
        beta = jnp.exp(m_blk - m_new)                    # rescale new block
        l = l * alpha + l_blk * beta
        scale_old = jnp.moveaxis(alpha, 1, 2)[..., None]  # (B, Tq, H, 1)
        scale_new = jnp.moveaxis(beta, 1, 2)[..., None]
        o = o * scale_old + o_blk.astype(jnp.float32) * scale_new
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        extras = tuple(jax.lax.ppermute(e, axis_name, perm) for e in extras)
        return o, m_new, l, k, v, extras

    o, m, l, _, _, _ = jax.lax.fori_loop(0, n, body, (o, m, l, k, v, extras))
    l = jnp.maximum(l, 1e-30)                            # fully-masked rows -> 0
    out = o / jnp.moveaxis(l, 1, 2)[..., None]
    return out.astype(q.dtype)


def ring_attention_shard(q, k, v, axis_name: str, causal: bool = True, vary_axes=()):
    """Per-shard (plain causal/full) ring attention body; call inside
    shard_map."""
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def scores(qf, kf, extras, q0, k0):
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", qf, kf, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qpos = q0 + jnp.arange(qf.shape[1])
            kpos = k0 + jnp.arange(kf.shape[1])
            s = jnp.where((qpos[:, None] >= kpos[None, :])[None, None], s, NEG_INF)
        return s

    return _ring_loop(q, k, v, (), axis_name, scores, vary_axes)


def ring_self_attention(
    q,
    k,
    v,
    mesh: Mesh,
    causal: bool = True,
    seq_axis: str = "sp",
    batch_axis: Optional[str] = "dp",
):
    """Sequence-parallel attention over ``mesh``: shards T over ``seq_axis``
    (and B over ``batch_axis`` when present in the mesh)."""
    if seq_axis not in mesh.shape or mesh.shape[seq_axis] == 1:
        # no sequence sharding: plain blockwise attention on each device
        o, m, l = _block_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v, 0, 0, 1.0 / (q.shape[-1] ** 0.5), causal
        )
        return (o / jnp.moveaxis(jnp.maximum(l, 1e-30), 1, 2)[..., None]).astype(q.dtype)

    b_axis = batch_axis if batch_axis in mesh.shape else None
    spec = P(b_axis, seq_axis, None, None)
    fn = shard_map(
        functools.partial(
            ring_attention_shard, axis_name=seq_axis, causal=causal, vary_axes=(b_axis,)
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def masked_ring_attention_shard(
    q, k, v, key_mask, counts, slopes, axis_name: str,
    window: float = float(1 << 30), vary_axes=(),
):
    """Ring attention with the transformer seq-mode semantics: per-key
    observation masks, ALiBi bias over *observed-step* ages, ring-buffer
    eviction of keys older than ``window`` observed steps, self always
    visible — scores built by flash_attention._masked_scores, the single
    shared semantics definition.

    ``counts`` is the GLOBAL observed-count cumsum (computed over the full
    T before sharding — ages are differences of global counts, so each
    shard only needs its own slice).  key_mask/counts (B, T_loc) rotate
    around the ring with their K/V shard.
    """
    from .flash_attention import _masked_scores  # circular at module level

    scale = 1.0 / (q.shape[-1] ** 0.5)
    c_q = counts  # this shard's queries' observed counts (B, T_loc)
    slopes_f = slopes.astype(jnp.float32)

    def scores(qf, kf, extras, q0, k0):
        mask_k, c_k = extras
        s, _ = _masked_scores(
            qf, kf, c_q, c_k, mask_k, slopes_f, window, q0, scale, k0=k0
        )
        return s

    # key_mask/counts are sharded shard_map inputs — already device-varying
    return _ring_loop(q, k, v, (key_mask, counts), axis_name, scores, vary_axes)


def masked_ring_self_attention(
    q, k, v, key_mask, slopes,
    mesh: Mesh,
    window: int = 1 << 30,
    seq_axis: str = "sp",
    batch_axis: Optional[str] = "dp",
):
    """Sequence-parallel masked attention over ``mesh``: the transformer's
    training attention (flash_attention.masked_attention_reference
    semantics) with T sharded over ``seq_axis`` — long windows whose
    K/V no longer fit one chip ride the ICI ring instead.

    q/k/v (B, T, H, D); key_mask (B, T); slopes (H,).  The global
    observed-count cumsum is taken here, before sharding.
    """
    if seq_axis not in mesh.shape or mesh.shape[seq_axis] == 1:
        from .flash_attention import masked_attention_reference

        return masked_attention_reference(q, k, v, key_mask, slopes, window=window)
    counts = jnp.cumsum(key_mask.astype(jnp.float32), axis=1)

    b_axis = batch_axis if batch_axis in mesh.shape else None
    spec4 = P(b_axis, seq_axis, None, None)
    spec2 = P(b_axis, seq_axis)
    fn = shard_map(
        functools.partial(
            masked_ring_attention_shard,
            axis_name=seq_axis,
            window=float(window),
            vary_axes=(b_axis,),
        ),
        mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2, spec2, P(None)),
        out_specs=spec4,
    )
    return fn(q, k, v, key_mask, counts, slopes)


def full_attention_reference(q, k, v, causal: bool = True):
    """Naive O(T^2) attention for golden tests."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
