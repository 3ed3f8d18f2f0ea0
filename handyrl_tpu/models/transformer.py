"""Transformer policy/value nets with a KV-cache ring buffer as hidden state.

A model family beyond the reference's convnets/ConvLSTMs (SURVEY.md §2.2):
episode memory is a fixed-size per-layer key/value cache instead of an
RNN carry, so context is attention over the last ``memory_len`` steps.
The cache IS the hidden-state pytree, which makes the family drop-in
compatible with every existing path:

* acting — ``initial_state``/``apply(obs, hidden)`` step semantics, so the
  batched inference engine and agents work unchanged;
* training — the lax.scan hidden-carry path (parallel/train_step.py)
  trains it exactly like an RNN, burn-in included;
* export — the cache rides as the ``hidden0`` pytree of StableHLO
  artifacts (models/export.py).

Positions use ALiBi-style additive age biases (slope per head), so ring
wraparound needs no positional-embedding bookkeeping.  The cache write is
a one-hot blend — O(memory_len) per step, branch-free, XLA-friendly.

The sequence-parallel training path for very long windows is the ops
layer's ring attention (ops/ring_attention.py); this module is the
step-wise consumer of the same attention math.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn

NEG_INF = -1e30


def _alibi_slopes(n_heads: int) -> jnp.ndarray:
    """Geometric head slopes as in ALiBi: 2^(-8i/n)."""
    return jnp.asarray([2.0 ** (-8.0 * (i + 1) / n_heads) for i in range(n_heads)])


def _flatten_obs(obs, lead_dims: int = 1) -> jnp.ndarray:
    """Env-agnostic encoder input: flatten and concat every obs leaf,
    keeping the first ``lead_dims`` axes (batch, or batch+time)."""
    leaves = jax.tree_util.tree_leaves(obs)
    flat = [
        l.reshape(l.shape[:lead_dims] + (-1,)).astype(jnp.float32) for l in leaves
    ]
    return jnp.concatenate(flat, axis=-1)


class CachedSelfAttention(nn.Module):
    """Causal self-attention with two modes sharing one parameter set:

    * step mode — one decode-step over a KV ring buffer (acting path);
    * seq mode — a whole (B, T) window at once (training path): the
      ring-buffer semantics are reproduced exactly with masks, so both
      modes compute identical values: keys must be observed steps, ages
      count *observed* steps (matching the commit-masked cache writes),
      and keys older than ``memory_len`` observed steps are invisible
      (ring eviction).  Burn-in keys get stop_gradient, matching the
      scan path's no-grad warmup.
    """

    d_model: int
    n_heads: int
    memory_len: int

    @nn.compact
    def __call__(self, x, cache=None, slot=None, count=None, seq: bool = False,
                 key_mask=None, burn_in: int = 0, use_flash: bool = False,
                 ring_mesh=None, blk_q: int = 128, blk_k: int = 128):
        H, S = self.n_heads, self.memory_len
        Dh = self.d_model // H

        if not seq:
            B = x.shape[0]
            q = nn.Dense(H * Dh, name="q")(x).reshape(B, H, Dh)
            k_new = nn.Dense(H * Dh, name="k")(x).reshape(B, H, Dh)
            v_new = nn.Dense(H * Dh, name="v")(x).reshape(B, H, Dh)

            oh = jax.nn.one_hot(slot, S, dtype=x.dtype)[..., None, None]  # (B,S,1,1)
            k_cache = cache["k"] * (1 - oh) + oh * k_new[:, None]
            v_cache = cache["v"] * (1 - oh) + oh * v_new[:, None]

            scores = jnp.einsum("bhd,bshd->bhs", q, k_cache) / (Dh ** 0.5)
            idx = jnp.arange(S)
            age = (slot[:, None] - idx[None, :]) % S                      # 0 = newest
            valid = age < count[:, None]
            bias = -_alibi_slopes(H)[None, :, None] * age[:, None, :]
            scores = jnp.where(valid[:, None, :], scores + bias, NEG_INF)
            attn = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhs,bshd->bhd", attn, v_cache).reshape(B, H * Dh)
            return nn.Dense(self.d_model, name="o")(out), {"k": k_cache, "v": v_cache}

        # -- seq mode: (B, T, d_model) ------------------------------------
        B, T, _ = x.shape
        q = nn.Dense(H * Dh, name="q")(x).reshape(B, T, H, Dh)
        k = nn.Dense(H * Dh, name="k")(x).reshape(B, T, H, Dh)
        v = nn.Dense(H * Dh, name="v")(x).reshape(B, T, H, Dh)

        if burn_in > 0:  # scan parity: no gradients through warmup keys
            bmask = (jnp.arange(T) < burn_in).astype(x.dtype)[None, :, None, None]
            k = jax.lax.stop_gradient(k) * bmask + k * (1 - bmask)
            v = jax.lax.stop_gradient(v) * bmask + v * (1 - bmask)

        if key_mask is None:
            key_mask = jnp.ones((B, T), x.dtype)

        # named for the remat ladder (TransformerNet seq mode): under
        # jax.checkpoint with save_only_these_names('attn_qkv') these
        # projections — the flash kernel's custom-VJP residuals — stay
        # materialized while everything else in the block is recomputed,
        # so the kernel's own chunked backward never waits on a second
        # dense-projection replay
        from jax.ad_checkpoint import checkpoint_name

        q = checkpoint_name(q, "attn_qkv")
        k = checkpoint_name(k, "attn_qkv")
        v = checkpoint_name(v, "attn_qkv")

        # one semantics, three executions: the O(T^2) einsum reference
        # (masked_attention_reference — per-key masks, observed-age ALiBi,
        # ring-window eviction, self always visible), the O(T·blk) Pallas
        # kernel golden-tested against it
        # (tests/test_flash_attention.py::test_masked_flash_matches_reference),
        # or — when a mesh with an 'sp' axis is supplied — sequence-parallel
        # masked ring attention sharding T across chips
        if ring_mesh is not None:
            from ..ops.ring_attention import masked_ring_self_attention

            out = masked_ring_self_attention(
                q, k, v, key_mask, _alibi_slopes(H), ring_mesh, window=S
            )
        elif use_flash:
            from ..ops.flash_attention import masked_flash_attention

            out = masked_flash_attention(
                q, k, v, key_mask, _alibi_slopes(H), window=S,
                blk_q=blk_q, blk_k=blk_k,
            )
        else:
            from ..ops.flash_attention import masked_attention_reference

            out = masked_attention_reference(q, k, v, key_mask, _alibi_slopes(H), window=S)
        return nn.Dense(self.d_model, name="o")(out.reshape(B, T, H * Dh)), None


def _section(fn, names, sum_grads):
    """``fn(mdl, x, token) -> (y, token)`` as a lifted custom-VJP section
    that sums its own parameter gradient: the backward rule runs ``fn``'s
    backward pass and hands the cotangents of the submodules ``names``,
    ``x``'s cotangent and the token's to ``sum_grads`` (parallel/mesh.py
    ``sum_section_grads``: a sum over the data-parallel chips, ordered
    against the backward pass by the token), which returns all three."""

    def forward(mdl, x, token):
        return nn.vjp(fn, mdl, x, token)

    def backward(vjp_fn, cts):
        grads, x_t, _ = vjp_fn(cts)
        own = grads["params"]
        summed, x_t, token_t = sum_grads({k: own[k] for k in names if k in own}, x_t, cts[1])
        return {"params": {**own, **summed}}, x_t, token_t

    return nn.custom_vjp(fn, forward_fn=forward, backward_fn=backward)


class TransformerNet(nn.Module):
    """Generic memory-transformer policy/value net.

    ``num_actions`` sets the policy head; ``with_return`` adds the reward-sum
    head (Geister-style).  Observations of any pytree shape are flattened
    into the token encoder, so one family serves every bundled env.
    """

    num_actions: int
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    memory_len: int = 32
    mlp_ratio: int = 4
    with_return: bool = False
    supports_seq: bool = True  # train path may call with seq=True

    @nn.compact
    def __call__(self, obs, hidden=None, train: bool = False, *,
                 seq: bool = False, key_mask=None, burn_in: int = 0,
                 use_flash: bool = False, ring_mesh=None,
                 remat: str = "none", blk_q: int = 128, blk_k: int = 128,
                 sum_grads=None):
        # sections of the seq forward: plain calls, or (sum_grads given, a
        # data-parallel train step's) each one summing its own gradient,
        # with a token threaded through them for sum_grads to order by
        token = None if sum_grads is None else jnp.zeros((), jnp.float32)

        def section(fn, names, x):
            nonlocal token
            if sum_grads is None:
                return fn(self, x, None)[0]
            x, token = _section(fn, names, sum_grads)(self, x, token)
            return x

        def encode(mdl, flat, tok):
            x = nn.relu(nn.Dense(self.d_model, name="enc1")(flat))
            return nn.Dense(self.d_model, name="enc2")(x), tok

        if seq:
            x = section(encode, ("enc1", "enc2"), _flatten_obs(obs, 2))
            slot = count = None
        else:
            if hidden is None:
                leaves = jax.tree_util.tree_leaves(obs)
                hidden = self.initial_state((leaves[0].shape[0],))
            x = nn.relu(nn.Dense(self.d_model, name="enc1")(_flatten_obs(obs)))
            pos = hidden["pos"]                 # float32 (B,): scan-carry safe
            count = jnp.minimum(pos + 1, self.memory_len).astype(jnp.int32)
            slot = jnp.mod(pos, float(self.memory_len)).astype(jnp.int32)
            x = nn.Dense(self.d_model, name="enc2")(x)

        # selective-remat ladder (seq mode only; config: train_args.remat):
        #   none  — store every activation (fastest backward, most HBM);
        #   attn  — jax.checkpoint around each attention sublayer: the
        #           O(T^2) score/softmax tensors (einsum) or the kernel
        #           forward (flash) recompute in the backward pass;
        #   block — checkpoint the whole attention+FFN residual block:
        #           only block inputs (B, T, d) survive per layer, the
        #           lever that fits T1024 x d1536 in HBM.
        # Both rungs keep the q/k/v projections — the flash kernel's
        # custom-VJP residuals, tagged 'attn_qkv' in CachedSelfAttention —
        # materialized via save_only_these_names, so the kernel's chunked
        # backward starts from stored operands.  Param names/trees are
        # unchanged (flax lifted remat), so checkpoints stay compatible
        # and remat on/off is bit-identical under jit (pinned by
        # tests/test_transformer.py::test_seq_remat_bit_parity).
        if seq and remat not in ("none", "attn", "block"):
            raise ValueError(f"remat={remat!r} not one of ('none', 'attn', 'block')")
        pol = jax.checkpoint_policies.save_only_these_names("attn_qkv")

        new_layers = []
        for i in range(self.n_layers):
            # one definition of each block half, shared by every rung of
            # the ladder AND the step path — an edit to the block math
            # cannot diverge the executions
            def attn_sub(mdl, h, km, i=i):
                a, _ = CachedSelfAttention(
                    self.d_model, self.n_heads, self.memory_len, name=f"attn{i}"
                )(
                    h, seq=True, key_mask=km, burn_in=burn_in,
                    use_flash=use_flash, ring_mesh=ring_mesh,
                    blk_q=blk_q, blk_k=blk_k,
                )
                return a

            def mlp_half(mdl, x, i=i):
                h = nn.LayerNorm(name=f"ln_m{i}")(x)
                m = nn.Dense(self.mlp_ratio * self.d_model, name=f"mlp_up{i}")(h)
                return x + nn.Dense(self.d_model, name=f"mlp_dn{i}")(nn.relu(m))

            def block_fn(mdl, x, km, i=i):
                h = nn.LayerNorm(name=f"ln_a{i}")(x)
                return mlp_half(mdl, x + attn_sub(mdl, h, km))

            if not seq:
                h = nn.LayerNorm(name=f"ln_a{i}")(x)
                a, new_cache = CachedSelfAttention(
                    self.d_model, self.n_heads, self.memory_len, name=f"attn{i}"
                )(
                    h,
                    cache=hidden["layers"][i],
                    slot=slot,
                    count=count,
                    seq=False,
                    key_mask=key_mask,
                    burn_in=burn_in,
                    use_flash=use_flash,
                    ring_mesh=ring_mesh,
                )
                x = mlp_half(self, x + a)
                new_layers.append(new_cache)
            else:
                def block(mdl, x, tok, i=i):
                    if remat == "block":
                        x = nn.remat(block_fn, policy=pol)(mdl, x, key_mask)
                    elif remat == "attn":
                        h = nn.LayerNorm(name=f"ln_a{i}")(x)
                        x = mlp_half(mdl, x + nn.remat(attn_sub, policy=pol)(mdl, h, key_mask))
                    else:
                        x = block_fn(mdl, x, key_mask)
                    return x, tok

                own = tuple(f"{part}{i}" for part in ("ln_a", "attn", "ln_m", "mlp_up", "mlp_dn"))
                x = section(block, own, x)
                new_layers.append(None)

        def heads(mdl, x, tok):
            h = nn.LayerNorm(name="ln_f")(x)
            out: Dict[str, Any] = {
                "policy": nn.Dense(self.num_actions, name="policy")(h),
                "value": jnp.tanh(nn.Dense(1, name="value")(h)),
            }
            if self.with_return:
                out["return"] = nn.Dense(1, name="return_head")(h)
            return out, tok

        if seq:
            return section(heads, ("ln_f", "policy", "value", "return_head"), x)
        out = heads(self, x, None)[0]
        out["hidden"] = {"layers": tuple(new_layers), "pos": hidden["pos"] + 1.0}
        return out

    @nn.nowrap
    def initial_state(self, batch_dims: Sequence[int] = ()):
        bd = tuple(batch_dims)
        Dh = self.d_model // self.n_heads
        cache = lambda: {  # noqa: E731
            "k": jnp.zeros((*bd, self.memory_len, self.n_heads, Dh), jnp.float32),
            "v": jnp.zeros((*bd, self.memory_len, self.n_heads, Dh), jnp.float32),
        }
        # pos is float32 so the train step's observation-mask arithmetic on
        # the hidden carry (h * mask) never changes the carry dtype
        return {"layers": tuple(cache() for _ in range(self.n_layers)), "pos": jnp.zeros(bd, jnp.float32)}
