"""A policy/value trunk built from a layer-pattern string: ``M`` a Mamba-2
mixer, ``E`` a routed expert layer (with a shared expert where
``shared_width`` is not 0), ``*`` grouped-query causal attention (with
rotary positions where ``rope_theta`` is set), ``-`` a dense gated MLP
(SwiGLU), ``C`` compressed convolutional attention (grouped-query attention
in a latent whose queries and keys two causal convolutions mix over the last
steps: ``CompressedConvAttention``), ``L`` multi-head latent attention (keys
and values made from one low-rank latent a token, beside one rotated key part
that all heads share: ``LatentAttention``), ``W`` local attention: a ``*``
layer that sees ``window`` observed steps back, its own included (its step-mode
ring holds the fewer of ``window`` and ``memory_len`` slots); with
``rope_local_only`` the ``W`` layers alone rotate and the ``*`` layers beside
them rotate nothing.  ``*`` and ``W`` layers take two more options: ``qk_norm``
(an RMSNorm over each head of queries and keys, one scale of ``head_dim`` each,
before the rotation) and ``attn_gate`` (the core's output times the sigmoid of a
fifth projection of the layer's input, before ``o``).  Each layer is ``x + mixer(RMSNorm(x))``, or with
``sandwich`` ``x + RMSNorm(mixer(RMSNorm(x)))``; no biases but the conv's.
``out_scale_init`` is what the second norm's scale starts at: under 1, an
untrained stack is nearer the identity, as deep residual nets are started.
The stack is run ``loops`` times over its own weights: a final RMSNorm closes
every pass, its output feeds the next pass and, after the last, the heads,
and a gate on each pass's output gives the share of a token that would leave
the loop there (``exit_t``; nothing reads it but a counter: the heads read
the last pass).  ``loops`` 1, no ``sandwich`` and no ``rope_theta`` is the
``nemotron_h`` family's tower; ``"*-"`` layers with all three are a looped
dense transformer; a mixer and an ``E`` sub-layer in every layer
(``"MEME*E..."``) with ``residual_scale`` on every branch, ``embed_scale`` on
the encoder's output, ``logits_divisor`` under the policy logits,
``attn_score_scale`` on the attention scores, a ``"softmax"`` ``router`` over
the chosen logits and ``gated_experts`` is the ``granitemoehybrid`` family's
period; ``"CE"`` layers with a rotation of half of each head
(``rotary_factor``), top-1 gated experts, no shared expert and the ``"mlp"``
``router`` (a small MLP on a ``router_width``-wide representation that each
``E`` layer hands the next one's router, beside ``x`` and through every
checkpoint; gates the chosen expert's own probability, not renormalised) is
the ``zaya`` family's layer; ``W`` and ``*`` layers in one pattern
(``"W-*EWEWEWE"``) with ``rope_local_only``, ``qk_norm``, ``attn_gate``,
``sandwich``, ``embed_scale`` and renormalised ``sigmoid`` gates is the
``afmoe`` family's.  Every parameter is made and held in ``param_dtype`` (the
initialisers draw in it: a bfloat16 acting copy of a net too large for a
float32 tree is never preceded by one) and compute follows the parameters;
the state, decays, norms, router logits and softmaxes stay float32.  One
token is one player's observation at one env step:
the flattened observation through ``enc1``/``enc2`` stands where a language
model has its embedding, the policy/value/return heads where it has its
LM head (the same encoder and heads as ``TransformerNet``).

Parameters are per layer (``layer{i}``, used ``loops`` times: their gradient
is the sum over the uses); state is per *application*.  The hidden pytree
holds, for every (pass, layer) pair in pass-major order, what that mixer
carries between steps: the SSM state (float32) and the conv's last inputs
for ``M``, a ring of the last ``memory_len`` keys (rotated, where they are)
and values for ``*`` (for ``W`` of the last ``min(window, memory_len)``: the
pytree holds a ring as long as each layer sees back, ``ring(kind)``, and
``layout()`` lists them), for ``C`` that ring in its latent, the last rows of
queries and keys its convolutions look back on (``tail``) and the last
step's shifted value (``prev_v``), for ``L`` a ring of the last ``memory_len``
latents with their rotated key part (``latent``: nothing per head), nothing
for ``E`` and ``-``.  Like ``TransformerNet``
it has two modes over one parameter set:

* step mode — ``apply(obs, hidden)``: one step of every recurrence (acting,
  and the train step's scan path, which commits hidden only where observed);
  with ``rows=(player, begun)`` the mixers' states (all but ``pos``) are
  handed over per (row, player) and row ``player[n]`` of each is stepped
  where it lies, read as zeros where ``begun`` (``rows_in_place``; the
  streaming rollout of a game in which one player a lane acts);
* whole-window mode — ``seq=True``: a (rows, T) window at once, the scan in
  its chunked matmul form (a bfloat16 part's scan, skip, gate and norm as
  ``ops/ssd.py``'s one kernel where ``window_fits``; float32 keeps the lines
  to the bit).  The scan path's rules are kept exactly: an
  unobserved step (``key_mask`` 0) leaves every state as it was, which the
  window form gets by moving each row's observed steps to the front
  (``_compact``), running every mixer on that prefix, and moving the
  results back; burn-in steps run first, as a window of their own, and what
  they leave (SSM state, conv tails, last value, keys and values) is
  handed on under ``stop_gradient``.  A caller that knows, on the host, how many steps a
  row observes at most hands the packing over (``packed_order``: per window
  part the index of each row's i-th observed step, as many columns as that
  most): the mixers then run over that many steps, not over the window's.
  With ``loops`` over 1 the passes of a window are a ``lax.scan`` over the
  pass index (``scanned``): the stack is in the program once, its
  parameters closed over, the states and the ``remat: block`` checkpoints
  (one per layer application) stacked by pass; step mode unrolls them.
  Three or more equal periods with a ``C``, ``L`` or ``W`` layer behind what
  leads them are a ``lax.scan`` over the period index (``periods``); beside a
  ``W`` layer a ``*`` layer counts as one (``scanned_periods``): the period's
  attention layer is *told*, as data stacked by period, how far it sees and
  whether it rotates, so a global and a local layer share its program.

``E`` layers are told which experts they hold (``experts_held``,
``expert_offset``): they score and choose over all ``n_experts`` and add
their own experts' terms only (``ops/routed_experts.py``).  The window mode
returns, beside the heads, ``choices`` (per ``E`` layer the experts each
token chose, (rows, T, top_k)) and ``counters`` (the packed array's slots,
the observed steps, those the packing left out, with ``W`` layers the
query-key pairs causality lets through in them and those of these their window
masks, with ``attn_gate`` the mean gate, with ``L`` layers the values
they hand from burn-in to the forward part and what a head's keys and values
of those steps would be, with ``E`` layers the
rows the held experts computed, the slots of the row buffers they were
computed in and the passes past the first those took, and with ``loops``
over 1 the layer applications of a forward and the mean ``exit`` share the
last pass is left with); ``forward_prediction`` hands both on.  Step mode
returns the heads and the hidden alone (its callers iterate over them): an
``E`` layer *sows* its rows, its buffer's slots and its choices into the
``counters`` and ``choices`` collections, which a caller that wants them makes
mutable (``runtime/device_rollout.py`` ``build_streaming_fn(counters=True)``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np

from ..ops import attention_core, latent_core
from ..ops.routed_experts import choose, held_mix, open_sinks, reads_in_place
from ..ops.rows import COMMIT_SCOPE, acting_rows, begin_rows, put_rows
from ..ops.ssd import ssd_chunked, ssd_step, ssd_step_rows, ssd_window, window_fits
from .transformer import NEG_INF, _flatten_obs

# Mamba-2, routed experts, attention, gated MLP, compressed convolutional attention, latent attention,
# local (windowed) attention
KINDS = "ME*-CLW"
# ``jax.named_scope``s round the dense trunk's phases: a component of each of
# their ops' ``op_name`` in a device profile, forward and backward (the
# benchmark's ``mlp_roofline``, ``attn_step_share`` and ``norm_step_share``
# import them; docs/observability.md has the naming rule).  ``attn`` holds
# the projections, ``rope`` and ``gqa``, and in a ``C`` mixer ``cca_mix``: what
# it does to queries, keys and values between the projections and the rotation;
# in an ``L`` mixer ``mla_proj`` (its projections, the latent's norm, and what
# the latent-to-heads map does in either form) and ``mla_core`` (scores, mask,
# softmax and mix); in a ``*`` or ``W`` mixer ``attn_proj`` (q, k, v, o and the gate's
# projection), ``qk_norm`` (the per-head norms of queries and keys) and
# ``attn_gate`` (the gate's sigmoid and its product with the core's output)
ATTN_SCOPE, ROPE_SCOPE, GQA_SCOPE, MLP_SCOPE, NORM_SCOPE = "attn", "rope", "gqa", "mlp", "norm"
ATTN_PROJ_SCOPE, QK_NORM_SCOPE, ATTN_GATE_SCOPE = "attn_proj", "qk_norm", "attn_gate"
CCA_SCOPE = "cca_mix"
MLA_PROJ_SCOPE, MLA_CORE_SCOPE = "mla_proj", "mla_core"
_EXACT = jax.lax.Precision.HIGHEST     # moving rows about must not round them


def _dense(features: int, name: str, param_dtype=jnp.float32):
    return nn.Dense(features, use_bias=False, name=name, param_dtype=param_dtype)


def _gated(a_b):
    """``silu(a) * b`` over the two halves of one fused product's output."""
    a, b = jnp.split(a_b, 2, axis=-1)
    return jax.nn.silu(a) * b


def _rms(x, scale, eps: float, groups: int = 1):
    """RMSNorm over the last axis in ``groups`` equal parts, in float32."""
    shape = x.shape
    y = x.astype(jnp.float32).reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = y * jax.lax.rsqrt(jnp.square(y).mean(axis=-1, keepdims=True) + eps)
    return (y.reshape(shape) * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta: float):
    """Rotary positions over the whole last axis of ``x`` (N, L, ..., D),
    rotate-half pairing (d with d + D/2), at ``pos`` (N, L); in float32."""
    cos, sin = _turns(x, pos, theta)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _turns(x, pos, theta: float):
    """(cos, sin) of the angles the D/2 pairs of ``x`` (N, L, ..., D) turn
    by at ``pos`` (N, L), shaped to broadcast against a half of ``x``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[..., None] * inv_freq            # (N, L, D/2)
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    return jnp.cos(angle), jnp.sin(angle)


def _rope_pairs(x, pos, theta: float):
    """Rotary positions over the whole last axis of ``x`` (N, L, ..., R),
    adjacent pairing (2j with 2j + 1, by ``pos * theta ** (-2j / R)``), at
    ``pos`` (N, L); float32 in and out."""
    cos, sin = _turns(x, pos, theta)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _period(pattern: str) -> str:
    """The shortest string that ``pattern`` repeats (itself where none does)."""
    for width in range(1, len(pattern) + 1):
        if len(pattern) % width == 0 and pattern[:width] * (len(pattern) // width) == pattern:
            return pattern[:width]
    return pattern


def _periods(pattern: str):
    """(lead, repeat): the fewest leading layers behind which ``pattern`` is
    three or more repetitions of ``repeat`` (``"L-LELELELE"``: 2, ``"LE"``);
    (the pattern's length, ``""``) where it never is."""
    for lead in range(len(pattern)):
        repeat = _period(pattern[lead:])
        if len(pattern) - lead >= 3 * len(repeat):
            return lead, repeat
    return len(pattern), ""


def _compact(key_mask, order=None):
    """(place (N, L, T), valid (N, L), dropped ()): ``place[n, i, t]`` is 1
    where step ``t`` is row ``n``'s ``i``-th observed step; ``valid[n, i]``
    says there is an ``i``-th.  ``order`` (N, L) int32 is that index where
    the caller made it (``train_step.pack_order``: ``L`` the most steps a row
    observes, ``T`` where a row has no ``i``-th); without it ``L`` is ``T`` and
    the order is found here.  ``dropped`` counts observed steps that no slot
    of a handed ``order`` holds: 0 unless it is too short."""
    seen = key_mask > 0
    steps = jnp.arange(seen.shape[1])
    found = order is None
    if found:   # observed steps first, T past a row's last
        there = steps[None, :] < seen.sum(axis=1, keepdims=True)
        order = jnp.where(there, jnp.argsort(~seen, axis=1, stable=True), steps.size)
    place = order[:, :, None] == steps[None, None, :]       # no step is step T
    dropped = 0 if found else seen.sum() - (place.any(axis=1) & seen).sum()
    return place, (order >= 0) & (order < steps.size), dropped


class Mamba2Mixer(nn.Module):
    d_model: int
    heads: int
    head_dim: int
    groups: int
    state_size: int
    conv_kernel: int
    chunk: int
    eps: float
    dt_min: float
    dt_max: float
    dt_floor: float
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u, state, valid=None):
        """u (N, L, d) with ``valid`` (N, L) a prefix mask, or (N, d) for
        one step; state {"ssm", "conv"} -> (out, new state).  A step whose
        state holds ``rows`` (player (N,), begun (N,)) is handed both per (row,
        player) and steps row ``player[n]`` of each where it lies."""
        H, P, G, S, K = self.heads, self.head_dim, self.groups, self.state_size, self.conv_kernel
        inner, conv_dim = H * P, H * P + 2 * G * S
        step = u.ndim == 2
        if step:
            u = u[:, None]
        n, length = u.shape[:2]

        def dt_bias_init(key, shape, dtype):
            # the inverse softplus of a log-uniform draw in [dt_min, dt_max]
            dt = jnp.exp(jax.random.uniform(key, shape)
                         * (np.log(self.dt_max) - np.log(self.dt_min)) + np.log(self.dt_min))
            dt = jnp.maximum(dt, self.dt_floor)
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

        def a_log_init(key, shape, dtype):
            return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0)).astype(dtype)

        kept = self.param_dtype
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(), (K, conv_dim), kept)
        conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,), kept)
        dt_bias = self.param("dt_bias", dt_bias_init, (H,), kept)
        a_log = self.param("A_log", a_log_init, (H,), kept)
        skip = self.param("D", nn.initializers.ones, (H,), kept)
        norm_scale = self.param("norm_scale", nn.initializers.ones, (inner,), kept)

        z, xbc, dt = jnp.split(_dense(inner + conv_dim + H, "in_proj", kept)(u),
                               [inner, inner + conv_dim], axis=-1)
        # causal depthwise conv over the last K - 1 inputs and this one
        rows = state.get("rows")    # ``ssm`` and ``conv`` per (row, player): the acting one's
        tail = state["conv"]
        if rows is not None:
            with jax.named_scope(COMMIT_SCOPE):
                tail = acting_rows(tail, *rows)
        tail = tail.astype(xbc.dtype)
        fed = jnp.concatenate([tail, xbc], axis=1)                   # (N, K - 1 + L, C)
        conv = sum(fed[:, k:k + length] * conv_w[k].astype(xbc.dtype) for k in range(K))
        xbc_c = jax.nn.silu(conv + conv_b.astype(xbc.dtype))
        if valid is None:
            new_tail = fed[:, length:]
        else:   # the last K - 1 inputs of the observed prefix
            last = valid.sum(axis=1)[:, None] + jnp.arange(K - 1)[None, :]
            new_tail = jnp.take_along_axis(fed, last[..., None], axis=1)
        x, B, C = jnp.split(xbc_c, [inner, inner + G * S], axis=-1)
        x = x.reshape(n, length, H, P)
        B, C = B.reshape(n, length, G, S), C.reshape(n, length, G, S)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        if valid is not None:
            dt = dt * valid[..., None]
        A = -jnp.exp(a_log.astype(jnp.float32))
        # a bfloat16 part's scan, skip, gate and norm as one kernel; float32 keeps the lines
        whole = not step and window_fits(x.dtype, length, H, P, G, S)
        with jax.named_scope("ssd"):
            if step:
                one = (x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], state["ssm"])
                if rows is None:
                    y, ssm = ssd_step(*one)
                else:   # both leaves' acting rows written where they lie, by one kernel
                    y, ssm, new_tail = ssd_step_rows(*one, *rows, (state["conv"], new_tail))
                y = y[:, None]
            elif whole:
                y, ssm = ssd_window(x, dt, A, B, C, z, skip, norm_scale, state["ssm"], self.eps)
            else:
                y, ssm = ssd_chunked(x, dt, A, B, C, state["ssm"], self.chunk)
        if not whole:
            y = y.astype(jnp.float32) + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
            y = y.reshape(n, length, inner) * jax.nn.silu(z.astype(jnp.float32))
            y = _rms(y, norm_scale, self.eps, groups=G).astype(u.dtype)
        out = _dense(self.d_model, "out_proj", kept)(y)
        return (out[:, 0] if step else out), {"ssm": ssm, "conv": new_tail.astype(jnp.float32)}


class ExpertLayer(nn.Module):
    d_model: int
    n_experts: int
    top_k: int
    expert_width: int
    shared_width: int           # 0: no shared expert
    routed_scale: float
    experts_held: int
    expert_offset: int
    router: str = "sigmoid"     # or "softmax": over the chosen logits, no bias; or "mlp"
    gated: bool = False         # experts and shared expert ``silu(a) * b``, not ``relu^2``
    param_dtype: Any = jnp.float32
    router_width: int = 0       # "mlp": the width of the router's own representation
    eps: float = 1e-5           # "mlp": of the RMSNorm on that representation

    def _mlp_scores(self, tokens, carry):
        """The ``mlp`` router: tokens (n, d), carry (n, router_width) the
        ``E`` layer before's representation or None -> (softmax scores (n, E)
        float32, this layer's representation: the next one's carry).
        ``r = tokens Wd + bd``, plus ``carry_scale`` x the carry where there
        is one; the scores are a two-hidden-layer GELU MLP's on ``RMSNorm(r)``.
        All of it float32 at exact precision: the scores choose."""
        kept, wide = self.param_dtype, self.router_width

        def dense(x, name, features, bias=True):
            kernel = self.param(name, nn.initializers.lecun_normal(), (x.shape[-1], features), kept)
            y = jnp.dot(x, kernel.astype(jnp.float32), precision=_EXACT)
            if bias:
                y = y + self.param(name + "_bias", nn.initializers.zeros, (features,),
                                   kept).astype(jnp.float32)
            return y

        r = dense(tokens.astype(jnp.float32), "router_down", wide)
        if carry is not None:
            r = r + self.param("carry_scale", nn.initializers.ones, (wide,),
                               kept).astype(jnp.float32) * carry
        z = _rms(r, self.param("router_norm", nn.initializers.ones, (wide,), kept), self.eps)
        z = jax.nn.gelu(dense(z, "router_fc1", wide), approximate=False)
        z = jax.nn.gelu(dense(z, "router_fc2", wide), approximate=False)
        return jax.nn.softmax(dense(z, "router_out", self.n_experts, bias=False), axis=-1), r

    @nn.compact
    def __call__(self, h, valid=None, carry=None, stacked=None):
        """h (..., d) tokens, valid (...), carry (..., router_width) float32
        or None (the ``mlp`` router's second stream: the router's
        representation of the ``E`` layer before, None at the first) ->
        (out, chosen (..., k) int32, counts: ``held_mix``'s, the rows the held
        experts computed and the row buffer's passes and slots, with the
        ``mlp`` router also the valid tokens' ``gates`` summed and their
        count; this layer's carry, None for the other routers).  ``stacked``
        (a scan over periods, ``HybridNet.periods``): (the periods' ``w1``
        stacked, their ``w2``, the period, ``held_mix``'s ``sinks``) in the
        place of this layer's own ``w1`` and ``w2``, which its parameters
        then lack; the sinks come back in ``counts``.  Applied with
        a mutable ``counters`` or ``choices`` collection (the acting path's
        callers that want them: step mode returns the heads alone) it also
        sows the rows held, the buffer's slots and the chosen there."""
        lead, d = h.shape[:-1], h.shape[-1]
        tokens = h.reshape(-1, d)
        ok = jnp.ones(tokens.shape[:1], bool) if valid is None else valid.reshape(-1)
        fan_in = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
                                                  batch_axis=(0,))
        if self.router not in ("sigmoid", "softmax", "mlp"):
            raise ValueError(f"router {self.router!r}: 'sigmoid', 'softmax' or 'mlp'")
        kept, fused = self.param_dtype, 2 if self.gated else 1   # a gated input matrix holds a and b
        if self.router != "mlp":
            router = self.param("router", nn.initializers.lecun_normal(), (d, self.n_experts), kept)
        if self.router != "softmax":
            # chooses only; no gradient reaches it (top-k's indices carry none)
            bias = self.param("score_bias", nn.initializers.zeros, (self.n_experts,), kept)
        if stacked is None:
            w1 = self.param("w1", fan_in, (self.experts_held, d, fused * self.expert_width), kept)
            w2 = self.param("w2", fan_in, (self.experts_held, self.expert_width, d), kept)
            at = ()
        else:
            w1, w2, *at = stacked
        with jax.named_scope("route"):
            if self.router == "mlp":
                # a gate is the chosen's own probability among all experts
                scores, carry = self._mlp_scores(
                    tokens, None if carry is None else carry.reshape(-1, self.router_width))
                chosen, gates = choose(scores, bias, self.top_k, self.routed_scale,
                                       renormalise=False)
                carry = carry.reshape(lead + (self.router_width,))
            else:
                logits = jnp.dot(
                    tokens.astype(jnp.float32), router.astype(jnp.float32), precision=_EXACT)
                if self.router == "sigmoid":
                    chosen, gates = choose(
                        jax.nn.sigmoid(logits), bias, self.top_k, self.routed_scale)
                else:   # the chosen's softmax: renormalising over them cancels the rest
                    chosen, gates = choose(jax.nn.softmax(logits, axis=-1),
                                           jnp.zeros((self.n_experts,), jnp.float32), self.top_k,
                                           self.routed_scale)
        out, counts = held_mix(tokens, chosen, gates, ok, w1.astype(h.dtype),
                               w2.astype(h.dtype), self.expert_offset, self.n_experts,
                               self.gated, *at)
        if self.router == "mlp":
            counts = dict(counts, gates=jnp.where(ok[:, None], gates, 0.0).sum(),
                          gated=ok.sum() * self.top_k)
        if self.shared_width:
            with jax.named_scope("shared_expert"):
                up = _dense(fused * self.shared_width, "shared_up", kept)(tokens)
                out = out + _dense(d, "shared_down", kept)(
                    _gated(up) if self.gated else jnp.square(nn.relu(up)))
        if not self.is_initializing():  # no-ops but under a caller's ``mutable``
            self.sow("counters", "rows_held", counts["rows"].sum())
            self.sow("counters", "buffer_slots", counts["slots"])
            self.sow("counters", "slots_run", counts["blocks_run"])
            self.sow("choices", "chosen", chosen.reshape(lead + (self.top_k,)))
        return (out.reshape(lead + (d,)), chosen.reshape(lead + (self.top_k,)), counts,
                carry if self.router == "mlp" else None)


class GroupedQueryAttention(nn.Module):
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    memory_len: int             # the steps back a query sees: a local layer's window
    rope_theta: float = 0.0     # 0: no positions (order is left to other mixers)
    score_scale: float = 0.0    # what scores are multiplied by; 0: 1 / sqrt(head_dim)
    param_dtype: Any = jnp.float32
    qk_norm: bool = False       # an RMSNorm a head on queries and keys, before the rotation
    gated: bool = False         # the core's output times ``sigmoid(gate(h))``, before ``o``
    eps: float = 1e-5           # ``qk_norm``'s

    @nn.compact
    def __call__(self, h, state, valid=None):
        """Window mode: h (N, L, d) with ``valid`` a prefix mask, state
        {"k", "v" (N, L0, kv, D), "n" (N,)} the observed steps before this
        window.  Step mode: h (N, d), state {"k", "v" (N, memory_len, kv,
        D), "pos" (N,)} a ring, or with ``rows`` (player (N,), begun (N,)) the
        rings per (row, player), read and written at ``player[n]`` where they
        lie.  Returns (out, new state).  With
        ``rope_theta`` queries and keys are rotated by their position among
        the row's observed steps, and the keys are kept rotated; with
        ``qk_norm`` each head of both is normed over its ``head_dim`` values
        first (``q_norm``, ``k_norm``: one scale each, every head's); with
        ``gated`` the core's output is multiplied by the sigmoid of a fifth
        projection of ``h`` (``gate``, heads x D wide) before ``o``, and a
        window's new state also holds ``gate``: the valid tokens' mean gate,
        summed (the net takes it out again, for a counter).  A window's state
        may *tell* the layer what its fields otherwise fix (a scanned period's
        layers share one program: ``HybridNet`` ``periods``): ``reach`` ()
        int32 in the place of ``memory_len``, ``turn`` () int32 that positions
        are multiplied by before the rotation (0: nothing turns)."""
        with jax.named_scope(ATTN_SCOPE):
            return self._attend(h, state, valid)

    def _attend(self, h, state, valid):
        Hq, Hk, D = self.heads, self.kv_heads, self.head_dim
        step = h.ndim == 2
        if step:
            h = h[:, None]
        n, length = h.shape[:2]
        # (N, L, heads x D): head h is columns h x D .. (h + 1) x D
        kept = self.param_dtype
        with jax.named_scope(ATTN_PROJ_SCOPE):
            q, k, v = (_dense(Hq * D, "q", kept)(h), _dense(Hk * D, "k", kept)(h),
                       _dense(Hk * D, "v", kept)(h))
            gate = _dense(Hq * D, "gate", kept)(h) if self.gated else None
        if self.qk_norm:
            with jax.named_scope(QK_NORM_SCOPE):
                q, k = (_rms(x.reshape(n, length, -1, D), self.param(
                    name, nn.initializers.ones, (D,), kept), self.eps).reshape(x.shape)
                    for x, name in ((q, "q_norm"), (k, "k_norm")))
        # a window part whose rows the kernel holds whole runs rotation, scores,
        # mask, softmax and mix there, on the projections' own layout: chosen
        # from dtype and shape alone (the kernel scales by 1 / sqrt(head_dim))
        if not step and not self.score_scale and attention_core.fits(
                q.dtype, length, state["k"].shape[1], Hq, Hk, D):
            with jax.named_scope(GQA_SCOPE):
                out, new_state = _whole_rows(q, k, v, state, valid, Hq, self.memory_len,
                                             self.rope_theta)
        else:
            q = q.reshape(n, length, Hk, Hq // Hk, D)
            k, v = k.reshape(n, length, Hk, D), v.reshape(n, length, Hk, D)
            reach = state.get("reach", self.memory_len)
            if self.rope_theta:
                with jax.named_scope(ROPE_SCOPE):
                    at = state["pos"][:, None] if step else (
                        state["n"][:, None] + jnp.arange(length)[None, :])
                    if "turn" in state:     # a told layer: 0 leaves every angle 0
                        at = at * state["turn"]
                    q, k = _rope(q, at, self.rope_theta), _rope(k, at, self.rope_theta)
            with jax.named_scope(GQA_SCOPE):
                out, new_state = _grouped_rows(q, k, v, state, valid, step, reach,
                                               self.score_scale)
            out = out.reshape(n, length, Hq * D)
        if gate is not None:
            with jax.named_scope(ATTN_GATE_SCOPE):
                opened = jax.nn.sigmoid(gate.astype(jnp.float32))
                out = (out.astype(jnp.float32) * opened).astype(out.dtype)
                if not step:
                    new_state["gate"] = jnp.where(valid, opened.mean(axis=-1), 0.0).sum()
        with jax.named_scope(ATTN_PROJ_SCOPE):
            out = _dense(self.d_model, "o", kept)(out)
        return (out[:, 0] if step else out), new_state


def _grouped_rows(q, k, v, state, valid, step: bool, memory_len: int, score_scale: float = 0.0):
    """Grouped-query attention's einsum lines, q (N, L, Hk, Hq / Hk, D) and
    k, v (N, L, Hk, D) rotated where they are to be: the ring write (step
    mode) or the hand-off's concatenation (window mode), mask, scores,
    softmax and mix -> (out (N, L, Hk, Hq / Hk, D), new state: ``k``, ``v``
    and in window mode ``n``)."""
    (n, length), D = q.shape[:2], q.shape[-1]
    if step:
        S = memory_len
        slot = jnp.mod(state["pos"], float(S)).astype(jnp.int32)
        hot = jax.nn.one_hot(slot, S, dtype=jnp.float32)[..., None, None]
        rows, ring_k, ring_v = state.get("rows"), state["k"], state["v"]
        if rows is not None:    # rings per (row, player): the acting player's of each
            with jax.named_scope(COMMIT_SCOPE):
                ring_k, ring_v = acting_rows(ring_k, *rows), acting_rows(ring_v, *rows)
        keys = ring_k * (1 - hot) + hot * k.astype(jnp.float32)
        values = ring_v * (1 - hot) + hot * v.astype(jnp.float32)
        age = jnp.mod(slot[:, None] - jnp.arange(S)[None, :], S)
        allowed = (age < jnp.minimum(state["pos"] + 1, S)[:, None])[:, None, :]
        if rows is None:
            new_state = {"k": keys, "v": values}
        else:   # what ``keys`` and ``values`` are, written where the rings lie: the
            # step's slot alone, over zeros where the row's game has just begun
            with jax.named_scope(COMMIT_SCOPE):
                player, begun = rows
                new_state = {name: begin_rows(state[name], player, begun, whole=True).at[
                    jnp.arange(n), player, slot].set(new[:, 0].astype(jnp.float32))
                    for name, new in (("k", k), ("v", v))}
    else:
        before = state["n"].astype(jnp.int32)
        keys = jnp.concatenate([state["k"].astype(k.dtype), k], axis=1)
        values = jnp.concatenate([state["v"].astype(v.dtype), v], axis=1)
        allowed = _seen_from(before, state["k"].shape[1], valid, memory_len)
        new_state = {"k": keys, "v": values, "n": before + valid.sum(axis=1)}
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, keys.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scores = scores * score_scale if score_scale else scores / (D ** 0.5)
    scores = jnp.where(allowed[:, None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("ngrqk,nkgd->nqgrd", weights, values.astype(q.dtype)), new_state


def _seen_from(before, past: int, valid, memory_len: int):
    """(N, L, past + L) bool: which of a window part's keys (the ``past``
    slots handed over, ``before`` (N,) of them observed, then the part's own
    under ``valid`` (N, L)) each of its queries sees: those at or before it
    among the row's observed steps, fewer than ``memory_len`` back."""
    n, length = valid.shape
    # positions count observed steps: the past's come first
    key_pos = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(past)[None, :], (n, past)),
        before[:, None] + jnp.arange(length)[None, :]], axis=1)
    key_ok = jnp.concatenate([
        jnp.arange(past)[None, :] < before[:, None], valid], axis=1)
    query_pos = before[:, None] + jnp.arange(length)[None, :]
    gap = query_pos[:, :, None] - key_pos[:, None, :]
    return key_ok[:, None, :] & (gap >= 0) & (gap < memory_len)


def _whole_rows(q, k, v, state, valid, heads: int, memory_len: int, rope_theta: float):
    """A window part through ``ops/attention_core.py``'s kernel, q, k and
    v as the projections wrote them: -> (out (N, L, Hq x D), new state),
    the state's keys rotated as the einsum lines keep them.  A state that
    tells the layer its ``reach`` and ``turn`` hands both to the kernel."""
    (n, length), (past, Hk, D) = q.shape[:2], state["k"].shape[1:]
    before, count = state["n"].astype(jnp.int32), valid.sum(axis=1).astype(jnp.int32)
    past_k, past_v = state["k"].astype(k.dtype), state["v"].astype(v.dtype)
    told = () if "reach" not in state else (
        None, jnp.stack([state["reach"], state["turn"]]).astype(jnp.int32))
    out, keys = attention_core.attention_core(
        q, k, v, past_k.reshape(n, past, Hk * D), past_v.reshape(n, past, Hk * D),
        before, count, (heads // Hk, D, memory_len, rope_theta), *told)
    return out, {"k": jnp.concatenate([past_k, keys.reshape(n, length, Hk, D)], axis=1),
                 "v": jnp.concatenate([past_v, v.reshape(n, length, Hk, D)], axis=1),
                 "n": before + count}


class CompressedConvAttention(nn.Module):
    """Compressed convolutional attention (``C``): grouped-query attention
    in a latent of ``heads`` query and ``kv_heads`` key/value heads, whose
    queries and keys are mixed over the last steps by two causal
    convolutions before they meet.  With h the layer's normed input:

    * ``[q~; k~] = h [Wq; Wk]``; values with a shift: ``v = [h_t Wv1;
      h_{t-1} Wv2]``, each half ``kv_heads x head_dim / 2`` wide (with two
      key/value heads, head 0 is the current token's and head 1 the one
      before's);
    * ``z1`` a depthwise convolution of ``[q~; k~]`` over ``time0`` steps,
      ``z2`` a convolution of ``z1`` over ``time1`` steps in which each
      head's channels mix among themselves, both with bias, causal, the
      steps before a row's first read as zero rows of ``[q~; k~]``;
    * ``q = z2_q + (q~ + k~ of its key head) / 2``, ``k = z2_k + (the mean
      of its query heads' q~ + k~) / 2``;
    * per head ``q <- sqrt(D) q / |q|`` and ``k <- temp x sqrt(D) k / |k|``,
      ``temp`` one learned scalar a key head; rotary positions on the first
      ``rotary_dim`` of a head's dimensions, the keys kept rotated;
    * causal attention over the last ``memory_len`` observed steps, scores
      ``q . k / sqrt(D)``; ``o`` maps the ``heads x head_dim`` result back.

    Beside the key and value rings it carries ``tail``, the last ``time0 +
    time1 - 2`` rows of ``[q~; k~]``, and ``prev_v``, the last step's
    ``h Wv2``.  The value shift, both convolutions, the mean, the norms and
    the temperature are under ``cca_mix``; ``rope`` and ``gqa`` as in
    ``GroupedQueryAttention``, whose einsum lines and kernel it shares (the
    kernel is handed rotated operands and rotates nothing)."""

    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    memory_len: int
    rope_theta: float
    rotary_dim: int
    time0: int
    time1: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, state, valid=None):
        """As ``GroupedQueryAttention``; state also ``tail`` (N, time0 +
        time1 - 2, (heads + kv_heads) x D) and ``prev_v`` (N, kv_heads x D
        / 2), with ``rows`` per (row, player) as the rings."""
        with jax.named_scope(ATTN_SCOPE):
            return self._attend(h, state, valid)

    def _attend(self, h, state, valid):
        Hq, Hk, D, K0, K1 = self.heads, self.kv_heads, self.head_dim, self.time0, self.time1
        G, R, kept = Hq + Hk, Hq // Hk, self.param_dtype
        step = h.ndim == 2
        if step:
            h = h[:, None]
        n, length = h.shape[:2]
        grouped = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=(0, 2), out_axis=3, batch_axis=(1,))
        w0 = self.param("conv0_kernel", nn.initializers.lecun_normal(), (K0, G * D), kept)
        b0 = self.param("conv0_bias", nn.initializers.zeros, (G * D,), kept)
        w1 = self.param("conv1_kernel", grouped, (K1, G, D, D), kept)
        b1 = self.param("conv1_bias", nn.initializers.zeros, (G * D,), kept)
        temp = self.param("temp", nn.initializers.ones, (Hk,), kept)
        qk = jnp.concatenate([_dense(Hq * D, "q", kept)(h), _dense(Hk * D, "k", kept)(h)], axis=-1)
        v_now, v_next = (_dense(Hk * D // 2, "v_now", kept)(h),
                         _dense(Hk * D // 2, "v_prev", kept)(h))
        rows = state.get("rows")    # ``tail`` and ``prev_v`` per (row, player): the acting one's
        tail, prev_v = state["tail"], state["prev_v"]
        if rows is not None:
            with jax.named_scope(COMMIT_SCOPE):
                tail, prev_v = acting_rows(tail, *rows), acting_rows(prev_v, *rows)
        with jax.named_scope(CCA_SCOPE):
            fed = jnp.concatenate([tail.astype(qk.dtype), qk], axis=1)   # (N, K0 + K1 - 2 + L, C)
            shifted = jnp.concatenate([prev_v.astype(qk.dtype)[:, None], v_next], axis=1)
            if valid is None:
                new_tail, new_prev = fed[:, length:], shifted[:, length]
            else:   # what the observed prefix leaves
                count = valid.sum(axis=1)
                last = count[:, None] + jnp.arange(K0 + K1 - 2)[None, :]
                new_tail = jnp.take_along_axis(fed, last[..., None], axis=1)
                new_prev = jnp.take_along_axis(shifted, count[:, None, None], axis=1)[:, 0]
            v = jnp.concatenate([v_now, shifted[:, :length]], axis=-1).reshape(n, length, Hk, D)
            span = length + K1 - 1
            z1 = sum(fed[:, j:j + span] * w0[j].astype(qk.dtype) for j in range(K0))
            z1 = (z1 + b0.astype(qk.dtype)).reshape(n, span, G, D)
            z2 = sum(jnp.einsum("nlgd,gde->nlge", z1[:, j:j + length], w1[j].astype(qk.dtype))
                     for j in range(K1)) + b1.astype(qk.dtype).reshape(G, D)
            # float32 from here to the rotation: the mean, the norms, the temperature
            z2 = z2.astype(jnp.float32)
            q_in = qk[..., :Hq * D].astype(jnp.float32).reshape(n, length, Hk, R, D)
            k_in = qk[..., Hq * D:].astype(jnp.float32).reshape(n, length, Hk, D)
            q = z2[:, :, :Hq].reshape(n, length, Hk, R, D) + (q_in + k_in[:, :, :, None]) / 2
            k = z2[:, :, Hq:] + (q_in.mean(axis=3) + k_in) / 2
            unit = lambda x: x * jax.lax.rsqrt(                     # noqa: E731
                jnp.square(x).sum(axis=-1, keepdims=True) + 1e-12) * (D ** 0.5)
            q, k = unit(q), unit(k) * temp.astype(jnp.float32)[:, None]
        with jax.named_scope(ROPE_SCOPE):
            at = state["pos"][:, None] if step else (
                state["n"][:, None] + jnp.arange(length)[None, :])
            turn = lambda x: jnp.concatenate(                       # noqa: E731
                [_rope(x[..., :self.rotary_dim], at, self.rope_theta),
                 x[..., self.rotary_dim:]], axis=-1).astype(qk.dtype)
            q, k = turn(q), turn(k)
        with jax.named_scope(GQA_SCOPE):
            # a window part whose rows the kernel holds whole: from dtype and
            # shape alone, as in ``GroupedQueryAttention``
            if not step and attention_core.fits(q.dtype, length, state["k"].shape[1], Hq, Hk, D):
                out, new_state = _whole_rows(
                    q.reshape(n, length, Hq * D), k.reshape(n, length, Hk * D),
                    v.reshape(n, length, Hk * D), state, valid, Hq, self.memory_len, 0.0)
            else:
                out, new_state = _grouped_rows(q, k, v, state, valid, step, self.memory_len)
        if rows is None:
            new_state.update(tail=new_tail.astype(jnp.float32), prev_v=new_prev.astype(jnp.float32))
        else:
            with jax.named_scope(COMMIT_SCOPE):
                new_state.update(
                    tail=put_rows(state["tail"], new_tail.astype(jnp.float32), *rows),
                    prev_v=put_rows(state["prev_v"], new_prev.astype(jnp.float32), *rows))
        out = _dense(self.d_model, "o", kept)(out.reshape(n, length, Hq * D))
        return (out[:, 0] if step else out), new_state


class LatentAttention(nn.Module):
    """Multi-head latent attention (``L``; ``deepseek_v3`` without a query
    latent).  With x the layer's normed input, per token at position p (its
    index among the row's observed steps):

    * ``q = x Wq``, a head ``[qn (qk_nope); qr (qk_rope)]``;
    * ``[c~; kr~] = x Wkva``, ``c = RMSNorm(c~)`` the latent (``kv_latent``);
    * ``qr`` and ``kr~`` turn by p, adjacent pairs; ``kr`` is one key part
      for every head;
    * a head's ``[kn; v] = c Wkvb`` (``qk_nope`` and ``v_head`` wide);
    * scores ``(qn . kn + qr . kr) / sqrt(qk_nope + qk_rope)``, as two
      products summed (no key of both parts is ever made), causal over the
      last ``memory_len`` observed steps; ``o`` maps the ``heads x v_head``
      mix back.

    What it carries is ``latent``, ``[c; kr]`` a step in float32: nothing per
    head.  A window runs the *expanded* form (keys and values made from the
    latents, the past's with the part's own); a step the *absorbed* one: the
    query taken into the latent by ``Wkvb``'s key half, scores and mix
    against the ring as it lies, the value half applied to the mix.
    ``mla_proj`` holds the projections, the latent's norm and what ``Wkvb``
    does in either form, ``mla_core`` the scores, mask, softmax and mix.

    A window part in bfloat16 whose widths are whole 128-lane tiles
    (``qk_rope`` 64 or 128) and whose rows hold ``ROWS_MIN`` queries or more
    runs the queries' rotation and ``mla_core`` as ``ops/latent_core.py``'s
    whole-row kernel, on q and on ``latents @ Wkvb`` where the products wrote
    them (``latent_core.fits``; a ``model.attention_path`` event says which
    way a part went): float32 operands (the judge's forward, the tests'
    widths), the few burn-in steps and step mode keep the einsum lines."""

    d_model: int
    heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    kv_latent: int
    memory_len: int
    rope_theta: float
    eps: float
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, state, valid=None):
        """Window mode: h (N, L, d) with ``valid`` a prefix mask, state
        {"latent" (N, L0, kv_latent + qk_rope), "n" (N,)} the observed steps
        before this window.  Step mode: h (N, d), state {"latent" (N,
        memory_len, kv_latent + qk_rope), "pos" (N,)} a ring, with ``rows``
        per (row, player) as ``GroupedQueryAttention``'s.  Returns (out, new
        state)."""
        with jax.named_scope(ATTN_SCOPE):
            return self._attend(h, state, valid)

    def expanded(self, values: int) -> int:
        """What every head's key of both parts and value would hold of the
        slots that ``values`` kept values (``kv_latent + qk_rope`` a slot) are."""
        return values // (self.kv_latent + self.qk_rope) * self.heads * (
            self.qk_nope + self.qk_rope + self.v_head)

    def _attend(self, h, state, valid):
        H, Dn, Dr, Dv, C = self.heads, self.qk_nope, self.qk_rope, self.v_head, self.kv_latent
        kept, f32 = self.param_dtype, jnp.float32
        step = h.ndim == 2
        if step:
            h = h[:, None]
        n, length = h.shape[:2]
        # a window part whose rows the kernel holds whole, from dtype and shape alone
        whole = not step and latent_core.fits(
            h.dtype, length, state["latent"].shape[1], H, Dn, Dr, Dv)
        with jax.named_scope(MLA_PROJ_SCOPE):
            q = _dense(H * (Dn + Dr), "q", kept)(h)     # (N, L, heads x (Dn + Dr))
            qn, qr = jnp.split(q.reshape(n, length, H, Dn + Dr), [Dn], axis=-1)
            c, kr = jnp.split(_dense(C + Dr, "kv_a", kept)(h).astype(f32), [C], axis=-1)
            c = _rms(c, self.param("kv_norm", nn.initializers.ones, (C,), kept), self.eps)
            # a head's columns: its key part's, then its value's
            kv_b = self.param("kv_b", nn.initializers.lecun_normal(), (C, H * (Dn + Dv)), kept)
            kv_b = kv_b.astype(h.dtype).reshape(C, H, Dn + Dv)
        with jax.named_scope(ROPE_SCOPE):
            at = state["pos"][:, None] if step else (
                state["n"][:, None] + jnp.arange(length)[None, :])
            if not whole:   # the kernel turns the queries where they lie
                qr = _rope_pairs(qr, at, self.rope_theta).astype(h.dtype)
            kr = _rope_pairs(kr, at, self.rope_theta)
        new = jnp.concatenate([c, kr], axis=-1)         # (N, L, C + Dr) float32: all that is kept
        scale = (Dn + Dr) ** -0.5
        weigh = lambda scores: jax.nn.softmax(      # noqa: E731  (N, H, L, keys) under ``allowed``
            jnp.where(allowed[:, None], scores, NEG_INF), axis=-1).astype(h.dtype)
        if step:
            S = self.memory_len
            slot = jnp.mod(state["pos"], float(S)).astype(jnp.int32)
            hot = jax.nn.one_hot(slot, S, dtype=f32)[..., None]
            rows, ring = state.get("rows"), state["latent"]
            if rows is not None:    # rings per (row, player): the acting player's
                with jax.named_scope(COMMIT_SCOPE):
                    ring = acting_rows(ring, *rows)
            latents = ring * (1 - hot) + hot * new
            age = jnp.mod(slot[:, None] - jnp.arange(S)[None, :], S)
            allowed = (age < jnp.minimum(state["pos"] + 1, S)[:, None])[:, None, :]
            if rows is None:
                new_state = {"latent": latents}
            else:   # the step's slot alone, over zeros where the row's game has just begun
                with jax.named_scope(COMMIT_SCOPE):
                    player, begun = rows
                    new_state = {"latent": begin_rows(
                        state["latent"], player, begun, whole=True).at[
                        jnp.arange(n), player, slot].set(new[:, 0])}
            if not self.is_initializing():  # no-ops but under a caller's ``mutable``
                self.sow("counters", "latent_state_values", jnp.float32(latents.size))
                self.sow("counters", "expanded_state_values", jnp.float32(
                    self.expanded(latents.size)))
            with jax.named_scope(MLA_PROJ_SCOPE):       # the query into the latent
                q_in = jnp.einsum("nqhd,chd->nqhc", qn, kv_b[..., :Dn],
                                  preferred_element_type=f32).astype(h.dtype)
            with jax.named_scope(MLA_CORE_SCOPE):
                past = latents.astype(h.dtype)
                scores = (jnp.einsum("nqhc,nkc->nhqk", q_in, past[..., :C],
                                     preferred_element_type=f32)
                          + jnp.einsum("nqhr,nkr->nhqk", qr, past[..., C:],
                                       preferred_element_type=f32)) * scale
                mix = jnp.einsum("nhqk,nkc->nqhc", weigh(scores), past[..., :C],
                                 preferred_element_type=f32).astype(h.dtype)
            with jax.named_scope(MLA_PROJ_SCOPE):       # the value half, after the mix
                out = jnp.einsum("nqhc,chd->nqhd", mix, kv_b[..., Dn:])
        else:
            before, past = state["n"].astype(jnp.int32), state["latent"].shape[1]
            count = valid.sum(axis=1).astype(jnp.int32)
            latents = jnp.concatenate([state["latent"].astype(f32), new], axis=1)
            new_state = {"latent": latents, "n": before + count}
            if whole:
                # the queries' rotation, scores, mask, softmax and mix in
                # ``ops/latent_core.py``'s kernel, on q where its product wrote it and on
                # keys and values as one product writes them for every head, the part's
                # own before the past's (the order of the kernel's mask)
                with jax.named_scope(MLA_PROJ_SCOPE):
                    keys = jnp.concatenate([new, state["latent"].astype(f32)], axis=1).astype(h.dtype)
                    kv = keys[..., :C] @ kv_b.reshape(C, H * (Dn + Dv))
                with jax.named_scope(MLA_CORE_SCOPE):
                    out = latent_core.latent_core(
                        q, kv, keys[..., C:], before, count,
                        (Dn, Dr, Dv, self.memory_len, self.rope_theta))
            else:
                allowed = _seen_from(before, past, valid, self.memory_len)
                with jax.named_scope(MLA_PROJ_SCOPE):   # the past's keys and values with the part's own
                    kv = jnp.einsum("nkc,chd->nkhd", latents[..., :C].astype(h.dtype), kv_b)
                with jax.named_scope(MLA_CORE_SCOPE):
                    scores = (jnp.einsum("nqhd,nkhd->nhqk", qn, kv[..., :Dn],
                                         preferred_element_type=f32)
                              + jnp.einsum("nqhr,nkr->nhqk", qr, latents[..., C:].astype(h.dtype),
                                           preferred_element_type=f32)) * scale
                    out = jnp.einsum("nhqk,nkhd->nqhd", weigh(scores), kv[..., Dn:])
        with jax.named_scope(MLA_PROJ_SCOPE):
            out = _dense(self.d_model, "o", kept)(out.reshape(n, length, H * Dv))
        return (out[:, 0] if step else out), new_state


class GatedMLP(nn.Module):
    """``down(silu(gate(h)) * up(h))``, no biases; keeps no state."""

    d_model: int
    width: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, state, valid=None):
        kept = self.param_dtype
        with jax.named_scope(MLP_SCOPE):
            gated = (jax.nn.silu(_dense(self.width, "gate", kept)(h))
                     * _dense(self.width, "up", kept)(h))
            return _dense(self.d_model, "down", kept)(gated), state


class Layer(nn.Module):
    """``x + mixer(RMSNorm(x))``, with ``sandwich`` ``x + RMSNorm(mixer(
    RMSNorm(x)))``, the mixer's branch times ``residual_scale``; a mixer
    that keeps no state hands the state it was given back, one that routes
    says what it chose.  ``carry`` is the stack's second stream: what an
    ``E`` layer's router hands the next one's (``router: mlp``; None
    elsewhere), passed through by every other mixer.  ``stacked`` is an
    ``ExpertLayer``'s."""

    mixer: nn.Module
    eps: float
    sandwich: bool = False
    out_scale_init: float = 1.0     # what the second norm's scale starts at
    residual_scale: float = 1.0
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, state, valid, carry=None, stacked=None):
        with jax.named_scope(NORM_SCOPE):
            h = _rms(x, self.param("norm", nn.initializers.ones, (x.shape[-1],),
                                   self.param_dtype), self.eps)
        routed = None
        if isinstance(self.mixer, ExpertLayer):
            y, chosen, counts, carry = self.mixer(h, valid, carry, stacked)
            routed = (chosen, counts)
        else:
            y, state = self.mixer(h, state, valid)
        if self.sandwich:
            with jax.named_scope(NORM_SCOPE):
                y = _rms(y, self.param("norm_out", nn.initializers.constant(self.out_scale_init),
                                       (x.shape[-1],), self.param_dtype), self.eps)
        if self.residual_scale != 1.0:
            y = (self.residual_scale * y).astype(x.dtype)
        return x + y, state, routed, carry


class HybridNet(nn.Module):
    """``pattern`` spells the layers of the stack (``"MEMEM*EME"``,
    ``"*-*-"``), ``loops`` how many times it is run over its own weights;
    the widths default to a size tests run and are set by
    ``env_args['net_args']``."""

    num_actions: int
    pattern: str = "ME*"
    d_model: int = 64
    with_return: bool = False
    norm_eps: float = 1e-5
    # M: Mamba-2
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    n_groups: int = 2
    state_size: int = 16
    conv_kernel: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    # E: routed experts and a shared one
    n_experts: int = 8
    top_k: int = 2
    expert_width: int = 32
    shared_width: int = 64
    routed_scale: float = 2.5
    experts_held: int = 8
    expert_offset: int = 0
    # *: grouped-query attention
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    memory_len: int = 32
    supports_seq: bool = True  # train path may call with seq=True
    rope_theta: float = 0.0    # *: rotary positions at this base; 0: none
    # W: a ``*`` layer that sees ``window`` observed steps back (its ring holds
    # the fewer of that and ``memory_len``); with ``rope_local_only`` the ``W``
    # layers alone rotate and ``*`` layers rotate nothing.  * and W: an RMSNorm
    # a head on queries and keys (``qk_norm``), a sigmoid gate on the core's
    # output (``attn_gate``)
    window: int = 0
    rope_local_only: bool = False
    qk_norm: bool = False
    attn_gate: bool = False
    # -: dense gated MLP
    mlp_width: int = 128
    # the stack: a second norm on each mixer's output (and what its scale
    # starts at), passes over the weights
    sandwich: bool = False
    out_scale_init: float = 1.0
    loops: int = 1
    # multipliers a published config may carry: on each mixer's branch, on the
    # encoder's output, under the policy logits (a divisor), on the attention
    # scores (0: 1 / sqrt(head_dim))
    residual_scale: float = 1.0
    embed_scale: float = 1.0
    logits_divisor: float = 1.0
    attn_score_scale: float = 0.0
    # E: "sigmoid" scores with a choosing bias, "softmax" over the chosen
    # logits, or "mlp": a small MLP's softmax on a ``router_width``-wide
    # representation that each ``E`` layer hands the next, the gates the
    # chosen's own probabilities; experts and shared expert gated
    # (``silu(a) * b``) or ``relu^2``
    router: str = "sigmoid"
    router_width: int = 32
    gated_experts: bool = False
    # C: the two convolutions' steps, and the share of a head's dimensions
    # that is rotated (heads, ``memory_len`` and ``rope_theta`` are ``*``'s)
    cca_time0: int = 2
    cca_time1: int = 2
    rotary_factor: float = 1.0
    # L: a head's unrotated and rotated query/key parts and its value, and the
    # latent they are made from (heads, ``memory_len``, ``rope_theta``: ``*``'s)
    qk_nope_dim: int = 16
    qk_rope_dim: int = 8
    v_head_dim: int = 16
    kv_latent: int = 32
    # what every parameter is made and held in ("bfloat16": an acting copy
    # that no float32 tree precedes); compute follows the parameters
    param_dtype: str = "float32"

    def _mixer(self, kind: str):
        # parentless: the Layer it is handed to adopts it, as ``mixer``
        kept = jnp.dtype(self.param_dtype)
        if kind == "M":
            return Mamba2Mixer(
                self.d_model, self.mamba_heads, self.mamba_head_dim, self.n_groups,
                self.state_size, self.conv_kernel, self.chunk, self.norm_eps,
                self.dt_min, self.dt_max, self.dt_floor, kept, parent=None)
        if kind == "E":
            return ExpertLayer(
                self.d_model, self.n_experts, self.top_k, self.expert_width, self.shared_width,
                self.routed_scale, self.experts_held, self.expert_offset, self.router,
                self.gated_experts, kept, self.router_width, self.norm_eps, parent=None)
        if kind == "-":
            return GatedMLP(self.d_model, self.mlp_width, kept, parent=None)
        if kind == "C":
            return CompressedConvAttention(
                self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.memory_len,
                self.rope_theta, 2 * int(self.head_dim * self.rotary_factor / 2), self.cca_time0,
                self.cca_time1, kept, parent=None)
        if kind == "L":
            return LatentAttention(
                self.d_model, self.n_heads, self.qk_nope_dim, self.qk_rope_dim, self.v_head_dim,
                self.kv_latent, self.memory_len, self.rope_theta, self.norm_eps, kept, parent=None)
        return GroupedQueryAttention(
            self.d_model, self.n_heads, self.n_kv_heads, self.head_dim, self.ring(kind),
            self.rope_theta if self.turns(kind) else 0.0, self.attn_score_scale, kept,
            self.qk_norm, self.attn_gate, self.norm_eps, parent=None)

    @nn.nowrap
    def turns(self, kind: str) -> int:
        """1 where an attention layer of ``kind`` rotates (at ``rope_theta``), else 0."""
        return int(kind == "W" or not self.rope_local_only)

    @nn.nowrap
    def scanned_periods(self):
        """``_periods`` of the pattern; beside a ``W`` layer a ``*`` layer
        counts as one (``"W-*EWEWEWE"``: 2, ``"WE"``): a scanned period's
        attention layer is told, as data, how far it sees and whether it
        rotates, so a local and a global layer share the period's program."""
        return _periods(self.pattern.replace("*", "W") if "W" in self.pattern else self.pattern)

    @nn.nowrap
    def ring(self, kind: str) -> int:
        """The steps back a mixer of ``kind`` sees, and the slots of its
        step-mode ring: a local layer's window where that is the fewer."""
        return min(self.window, self.memory_len) if kind == "W" else self.memory_len

    @staticmethod
    def _through(layers, x, states, valid):
        """Every layer once over ``x`` ((N, L, d) with ``valid``, or (N, d)),
        ``states`` this pass's: -> (x, new states, {layer: chosen}, {layer:
        counts}).  An ``mlp`` router's representation goes from each ``E``
        layer to the next beside ``x``, through every checkpoint between."""
        new_states, chosen, counts, carry = [], {}, {}, None
        for layer, state in zip(layers, states):
            x, state, routed, carry = layer(x, state, valid, carry)
            new_states.append(state)
            if routed is not None:
                chosen[layer.name], counts[layer.name] = routed
        return x, tuple(new_states), chosen, counts

    def _heads(self, h):
        kept = jnp.dtype(self.param_dtype)
        policy = nn.Dense(self.num_actions, name="policy", param_dtype=kept)(h)
        out: Dict[str, Any] = {
            "policy": policy if self.logits_divisor == 1.0 else policy / self.logits_divisor,
            "value": jnp.tanh(nn.Dense(1, name="value", param_dtype=kept)(h)),
        }
        if self.with_return:
            out["return"] = nn.Dense(1, name="return_head", param_dtype=kept)(h)
        return out

    @nn.compact
    def __call__(self, obs, hidden=None, train: bool = False, *,
                 seq: bool = False, key_mask=None, burn_in: int = 0, remat: str = "none",
                 packed_order=None, rows=None):
        if any(kind not in KINDS for kind in self.pattern):
            raise ValueError(f"pattern {self.pattern!r}: a layer is one of {KINDS!r}")
        if self.loops > 1 and "E" in self.pattern:
            # the choices are keyed by layer, and no reference takes a pass's
            raise ValueError(f"pattern {self.pattern!r} with loops {self.loops}: "
                             "a routed layer is run once")
        if self.loops > 1 and "C" in self.pattern:
            raise ValueError(f"pattern {self.pattern!r} with loops {self.loops}: "
                             "a compressed convolutional attention layer is run once")
        if self.loops > 1 and "L" in self.pattern:
            raise ValueError(f"pattern {self.pattern!r} with loops {self.loops}: "
                             "a latent attention layer is run once")
        if "L" in self.pattern and not self.rope_theta:
            raise ValueError(f"pattern {self.pattern!r}: a latent attention layer's rotated "
                             "part needs rope_theta")
        if "W" in self.pattern and self.window < 1:
            raise ValueError(f"pattern {self.pattern!r}: a local attention layer needs a window")
        kept = jnp.dtype(self.param_dtype)

        def encode(flat):
            # the trunk computes in its parameters' dtype (bf16 under
            # compute_dtype: bfloat16): _flatten_obs hands float32 over
            enc1 = nn.Dense(self.d_model, name="enc1", param_dtype=kept)
            x = nn.relu(enc1(flat))
            x = x.astype(enc1.variables["params"]["kernel"].dtype)
            x = nn.Dense(self.d_model, name="enc2", param_dtype=kept)(x)
            return x if self.embed_scale == 1.0 else x * self.embed_scale

        def layer(cls, kind, **where):
            return cls(self._mixer(kind), self.norm_eps, self.sandwich, self.out_scale_init,
                       self.residual_scale, kept, **where)

        # one module a layer, applied once a pass: its parameters exist once
        layers = lambda cls: [  # noqa: E731
            layer(cls, kind, name=f"layer{i}") for i, kind in enumerate(self.pattern)]
        norm_f = self.param("norm_f", nn.initializers.ones, (self.d_model,), kept)
        gate = nn.Dense(1, name="exit_gate", param_dtype=kept) if self.loops > 1 else None

        def close(x):       # the one final norm: ends every pass
            with jax.named_scope(NORM_SCOPE):
                return _rms(x, norm_f, self.norm_eps)

        def passes(stack, x, states, valid):
            """The stack ``loops`` times over ``x``, application (t, i) on
            ``states[t * len(pattern) + i]``, unrolled: step mode, ``init``
            (``scanned`` needs the parameters to exist), and a window without
            a loop, whose program stays what it was before there were loops.
            ``close`` ends every pass but the last, which the caller closes
            (over the window's steps, where that program has it): -> (x, new
            states, chosen, counts, stay), ``stay`` the share of each token
            no gate before the last pass let go (``exit_T``; None without a
            loop)."""
            depth, new_states = len(self.pattern), ()
            stay = None if gate is None else jnp.ones(x.shape[:-1], jnp.float32)
            for t in range(self.loops):
                x, new, chosen, counts = self._through(
                    stack, x, states[t * depth:(t + 1) * depth], valid)
                new_states += new
                if t < self.loops - 1:
                    x = close(x)
                    stay = stay * (1.0 - jax.nn.sigmoid(gate(x)[..., 0].astype(jnp.float32)))
            return x, new_states, chosen, counts, stay

        def scanned(x, states, valid):
            """A looped window's passes as a ``lax.scan`` over the pass index,
            each one ``stack; close; gate``, the last closed too (over the
            packed slots: the caller does not close again): the stack is in
            the program once, not ``loops`` times (a quarter of the compile
            and of the executable at four passes), its parameters closed
            over, application (t, i)'s state row ``t`` of layer ``i``'s
            stacked states, the checkpoints stacked likewise.  Each layer is
            applied as a function of its parameters (a bound module cannot
            be called under a jax transform), so they must exist: ``init``
            goes through ``passes``."""
            params, depth = self.variables["params"], len(self.pattern)

            def application(kind):
                free = layer(Layer, kind, parent=None)
                fn = lambda p, x, state: free.apply({"params": p}, x, state, valid)[:2]  # noqa: E731
                return fn if remat == "none" else jax.checkpoint(fn)

            stack = [application(kind) for kind in self.pattern]
            exit_gate = nn.Dense(1, parent=None)

            def one_pass(carry, this):
                (x, stay), (t, states_t) = carry, this
                new = []
                for i, apply in enumerate(stack):
                    x, state = apply(params[f"layer{i}"], x, states_t[i])
                    new.append(state)
                x = close(x)
                go = jax.nn.sigmoid(exit_gate.apply(
                    {"params": params["exit_gate"]}, x)[..., 0].astype(jnp.float32))
                # the last pass keeps what reaches it: its gate lets nothing go
                stay = stay * jnp.where(t == self.loops - 1, 1.0, 1.0 - go)
                return (x, stay), tuple(new)

            by_layer = tuple(jax.tree.map(lambda *rows: jnp.stack(rows), *states[i::depth])
                             for i in range(depth))
            (x, stay), new = jax.lax.scan(
                one_pass, (x, jnp.ones(x.shape[:-1], jnp.float32)),
                (jnp.arange(self.loops), by_layer))
            new_states = tuple(jax.tree.map(lambda rows: rows[t], new[i])
                               for t in range(self.loops) for i in range(depth))
            return x, new_states, {}, {}, stay

        def periods(x, states, valid):
            """A window's stack of equal periods (``"CECECE"``: three of
            ``"CE"``; ``"L-LELELELE"``: four of ``"LE"`` behind two leading
            layers, which run unrolled first) as a ``lax.scan`` over the period
            index: one period is in the program, not every layer (unrolled, the
            twelve sub-layers of ``zaya1_train_t192``'s step were a 518 MB
            executable, which jax's compile cache refuses, and two minutes of
            compile in every run: PERF.md, PR 48; the ten of
            ``kanana2_train_t192``'s left a cold run 21 s of its 330: PR 52).
            The periods' parameters, states and checkpoints are stacked by period, layer by layer; an ``mlp`` router's carry
            rides in the scan's carry beside ``x``, zeros into the first
            period under a ``carry_scale`` of zeros (the first ``E`` layer has
            none: it adds nothing).  As in ``scanned`` each layer is applied
            as a function of its parameters, so they must exist.

            The scan slices a period's leaves out of those stacks, and XLA
            fuses such a slice into a product of its own; a kernel's operand
            it copies out first.  So where the experts' products are the
            grouped kernel's (``reads_in_place``: bfloat16) an ``E`` layer's
            ``w1`` and ``w2`` do not go through the scan: it closes over their
            stacks, the kernel reads the period it is told where it lies,
            and the stacks' gradient comes back through sinks in the scan's
            carry, written a period at a time where it lies (``ops/
            routed_experts.py`` ``held_mix``; PERF.md, PR 51: the copies out
            and back were 21 of ``zaya1_train_t192``'s 122.5 ms)."""
            params, width = self.variables["params"], len(repeat)
            count = (len(self.pattern) - lead) // width
            x, led, chosen, counts = self._through(stack[:lead], x, states[:lead], valid)
            wide = self.router == "mlp" and "E" in repeat
            held = ("w1", "w2") if reads_in_place(x.dtype) else ()

            def application(kind):
                free = layer(Layer, kind, parent=None)
                fn = lambda p, x, state, carry, stacked: free.apply(  # noqa: E731
                    {"params": p}, x, state, valid, carry, stacked)
                return fn if remat == "none" else jax.checkpoint(fn)

            def of_period(i):
                """Layer ``i`` of every period, its leaves stacked: the scan's
                (all but an ``E`` layer's ``held``) -> the held ones' too."""
                each = [params[f"layer{lead + t * width + i}"] for t in range(count)]
                if wide and repeat[i] == "E" and "carry_scale" not in each[0]["mixer"]:
                    first = dict(each[0]["mixer"], carry_scale=jnp.zeros_like(
                        each[1]["mixer"]["carry_scale"]))
                    each[0] = dict(each[0], mixer=first)
                apart = None
                if repeat[i] == "E" and held:
                    apart = tuple(jnp.stack([p["mixer"][w].astype(x.dtype) for p in each])
                                  for w in held)
                    each = [dict(p, mixer={k: v for k, v in p["mixer"].items() if k not in held})
                            for p in each]
                return jax.tree.map(lambda *rows: jnp.stack(rows), *each), apart

            period = [application(kind) for kind in repeat]
            scanned_over, apart = zip(*(of_period(i) for i in range(width)))
            read = jax.lax.stop_gradient(apart)

            def one_period(carry, this):
                (x, handed, sinks), (t, p_t, states_t) = carry, this
                new, routed, sinks = [], [], list(sinks)
                for i, apply in enumerate(period):
                    stacked = None if read[i] is None else (*read[i], t, sinks[i])
                    x, state, chose, handed = apply(p_t[i], x, states_t[i], handed, stacked)
                    if stacked is not None:     # the sinks go on in the carry, not out with the counts
                        chose = chose[0], dict(chose[1])
                        sinks[i] = chose[1].pop("sinks")
                    new.append(state)
                    routed.append(chose)
                return (x, handed, tuple(sinks)), (tuple(new), tuple(routed))

            handed = jnp.zeros(x.shape[:-1] + (self.router_width,), jnp.float32) if wide else None
            def told(i):
                """What layer ``i`` of each period is told beside its state: an
                attention layer how far it sees and whether it turns."""
                kinds = self.pattern[lead + i::width]
                return {} if repeat[i] != "W" else {
                    "reach": jnp.array([self.ring(kind) for kind in kinds], jnp.int32),
                    "turn": jnp.array([self.turns(kind) for kind in kinds], jnp.int32)}

            by_layer = tuple(
                dict(jax.tree.map(lambda *rows: jnp.stack(rows), *states[lead + i::width]),
                     **told(i)) for i in range(width))
            # the sinks go in as the stacks themselves: what comes back for them is the gradient
            (x, _, sinks), (new, routed) = jax.lax.scan(
                one_period, (x, handed, apart), (jnp.arange(count), scanned_over, by_layer))
            x = open_sinks(x, sinks)
            at = lambda tree, t: jax.tree.map(lambda rows: rows[t], tree)  # noqa: E731
            new_states = led + tuple(at(new[i], t) for t in range(count) for i in range(width))
            for t in range(count):
                for i in range(width):
                    if routed[i] is not None:
                        name = f"layer{lead + t * width + i}"
                        chosen[name], counts[name] = at(routed[i], t)
            return x, new_states, chosen, counts, None

        if not seq:
            if hidden is None:
                hidden = self.initial_state((jax.tree.leaves(obs)[0].shape[0],))
            # step mode's ``rows`` (player (N,), begun (N,)): the leaves that
            # ``rows_in_place`` names are per (row, player), to be stepped at
            # ``player[n]`` where they lie and read as zeros where ``begun``
            where = {} if rows is None else {"rows": rows}
            at = dict(where, pos=hidden["pos"])
            given = {"M": where, "*": at, "C": at, "L": at, "W": at}
            states = tuple(
                dict(state, **given.get(kind, {}))
                for kind, state in zip(self.pattern * self.loops, hidden["layers"]))
            x, states, _, _, _ = passes(layers(Layer), encode(_flatten_obs(obs)), states, None)
            out = self._heads(close(x))
            out["hidden"] = {"layers": states, "pos": hidden["pos"] + 1.0}
            return out

        # -- whole window: (N, T, ...) -----------------------------------
        if remat not in ("none", "block"):   # no rung of its own for one mixer
            raise ValueError(f"HybridNet: remat={remat!r} not one of ('none', 'block')")
        x = encode(_flatten_obs(obs, 2))
        n, T = x.shape[:2]
        if key_mask is None:
            key_mask = jnp.ones((n, T), x.dtype)
        states = self._window_state(n, x.dtype)
        # one checkpoint per layer application where asked: only its input is kept
        stack = layers(Layer if remat == "none" else nn.remat(Layer))
        loop = gate is not None and not self.is_initializing()
        # three or more periods with a ``C``, an ``L`` or a ``W`` layer run as a scan
        # over them, behind what leads them (an ``mlp`` router's carry does not
        # cross from a leading ``E`` layer into the scan)
        lead, repeat = self.scanned_periods()
        repeats = (any(kind in repeat for kind in "CLW") and self.loops == 1
                   and not (self.router == "mlp" and "E" in self.pattern[:lead])
                   and not self.is_initializing())
        outs, chosen, counts = [], [], []
        slots = dropped = handed = pairs = cut = 0
        stayed = gates = 0.0
        before = jnp.zeros((n,), jnp.int32)     # a row's observed steps before the part
        for part, lo, hi in (("burn_in", 0, burn_in), ("forward", burn_in, T)):
            if lo == hi:
                continue
            # (N, L, hi - lo): L the part's length, or what the host found
            place, valid, lost = _compact(key_mask[:, lo:hi], (packed_order or {}).get(part))
            slots, dropped = slots + valid.size, dropped + lost
            place = place.astype(x.dtype)
            packed = jnp.einsum("nit,ntd->nid", place, x[:, lo:hi], precision=_EXACT)
            y, states, picked, count, stay = (
                scanned(packed, states, valid) if loop else periods(packed, states, valid)
                if repeats else passes(stack, packed, states, valid))
            # what the gated attention layers left beside their rings, taken out again
            gates = gates + sum(state["gate"] for state in states if "gate" in state)
            states = tuple({k: v for k, v in state.items() if k != "gate"} for state in states)
            if "W" in self.pattern:
                # the keys causality lets each query see (those at or before it among
                # the row's observed steps), and those of them ``window`` or more steps
                # back (the window's cut alone: what ``memory_len`` cuts is not counted)
                keys = jnp.where(valid, before[:, None] + jnp.arange(valid.shape[1]) + 1, 0)
                pairs = pairs + keys.sum()
                cut = cut + jnp.maximum(keys - self.window, 0).sum()
                before = before + valid.sum(axis=1).astype(jnp.int32)
            if hi == burn_in:   # scan parity: no gradient through what burn-in leaves
                states = jax.lax.stop_gradient(states)
                handed = sum(state["latent"].size for state in states if "latent" in state)
            outs.append(jnp.einsum("nit,nid->ntd", place, y, precision=_EXACT))
            spread = place.astype(jnp.int32)
            chosen.append({k: jnp.einsum("nit,nik->ntk", spread, v) for k, v in picked.items()})
            counts.append(count)
            if stay is not None:
                stayed = stayed + jnp.where(valid, stay, 0.0).sum()
        y = jnp.concatenate(outs, axis=1)
        out = self._heads(y if loop else close(y))
        # slots the mixers ran over, those of them that hold a token, and the
        # tokens no slot held (a handed packed_order that is too short)
        out["counters"] = {
            "packed_slots": jnp.float32(slots),
            "observed_steps": (key_mask > 0).sum().astype(jnp.float32),
            "packed_dropped": jnp.asarray(dropped, jnp.float32),
        }
        if self.loops > 1:
            # mixer applications a forward, and the mean over the packed
            # tokens of the share no gate let go before the last pass
            out["counters"].update(
                layer_applications=jnp.float32(self.loops * len(self.pattern)),
                exit_mass_last=stayed / jnp.maximum(
                    out["counters"]["observed_steps"] - out["counters"]["packed_dropped"], 1.0),
            )
        if "W" in self.pattern:
            # over the local layers: the query-key pairs causality lets through, and
            # those of them that the window masks
            local = self.loops * self.pattern.count("W")
            out["counters"].update(causal_pairs=(local * pairs).astype(jnp.float32),
                                   window_pairs_cut=(local * cut).astype(jnp.float32))
        if self.attn_gate and any(kind in self.pattern for kind in "*W"):
            # the mean of a gated attention layer's gate over its tokens and heads
            tokens = out["counters"]["observed_steps"] - out["counters"]["packed_dropped"]
            gated = self.loops * sum(self.pattern.count(kind) for kind in "*W")
            out["counters"]["attn_gate_mean"] = (
                gates / jnp.maximum(gated * tokens, 1.0)).astype(jnp.float32)
        if "L" in self.pattern:
            # what the ``L`` layers handed from burn-in to the forward part, and
            # what keys and values a head of the same steps would have been
            out["counters"].update(
                latent_state_values=jnp.float32(handed),
                expanded_state_values=jnp.float32(self._mixer("L").expanded(handed)))
        if chosen[0]:
            out["choices"] = {k: jnp.concatenate([c[k] for c in chosen], axis=1)
                              for k in chosen[0]}
            by_layer = jnp.stack(      # (layers, held)
                [sum(c[k]["rows"] for c in counts) for k in counts[0]])
            buffers = [c[k] for c in counts for k in c]     # one a routed layer and window part
            out["counters"].update(
                rows_held=by_layer.sum().astype(jnp.float32),
                expert_rows_max=by_layer.max().astype(jnp.float32),
                expert_rows_mean=by_layer.astype(jnp.float32).mean(),
                # the buffers' slots (every pass's), those of them in blocks that hold
                # a row, which the kernels ran, and the passes a buffer that
                # sufficed would not have taken
                buffer_slots=sum(b["slots"] for b in buffers).astype(jnp.float32),
                slots_run=sum(b["blocks_run"] for b in buffers).astype(jnp.float32),
                expert_passes=sum(b["passes"] - 1 for b in buffers).astype(jnp.float32),
            )
            if any("in_place" in b for b in buffers):   # not a leading layer's: it has no stack
                # routed layer applications whose kernels read their weights in the periods' stack
                out["counters"]["expert_stack_reads"] = sum(
                    b.get("in_place", 0) for b in buffers).astype(jnp.float32)
            if "gates" in buffers[0]:   # ``mlp``: the mean gate a token's result was scaled by
                out["counters"]["router_gate_mean"] = (
                    sum(b["gates"] for b in buffers)
                    / jnp.maximum(sum(b["gated"] for b in buffers), 1)).astype(jnp.float32)
        return out

    @nn.nowrap
    def _window_state(self, n: int, dtype):
        """What each mixer application carries into a window that nothing
        precedes."""
        states = []
        for kind, state in zip(self.pattern * self.loops, self.initial_state((n,))["layers"]):
            if kind in "*CW":   # C keeps its tail and last value as they start
                empty = jnp.zeros((n, 0, self.n_kv_heads, self.head_dim), dtype)
                state = dict(state, k=empty, v=empty, n=jnp.zeros((n,), jnp.int32))
            elif kind == "L":   # latents are handed on as the ring keeps them: float32
                state = {"latent": state["latent"][:, :0], "n": jnp.zeros((n,), jnp.int32)}
            states.append(state)
        return tuple(states)

    @nn.nowrap
    def initial_state(self, batch_dims: Sequence[int] = ()):
        bd = tuple(batch_dims)
        inner = self.mamba_heads * self.mamba_head_dim
        conv_dim = inner + 2 * self.n_groups * self.state_size
        zeros = lambda *shape: jnp.zeros(bd + shape, jnp.float32)  # noqa: E731
        layers = []
        for kind in self.pattern * self.loops:      # one state a (pass, layer)
            if kind == "M":
                layers.append({
                    "ssm": zeros(self.mamba_heads, self.mamba_head_dim, self.state_size),
                    "conv": zeros(self.conv_kernel - 1, conv_dim)})
            elif kind in "*CW":  # a ring as long as the layer sees back
                layers.append({
                    "k": zeros(self.ring(kind), self.n_kv_heads, self.head_dim),
                    "v": zeros(self.ring(kind), self.n_kv_heads, self.head_dim)})
                if kind == "C":
                    # the rows of ``[q~; k~]`` the convolutions look back on, the last value
                    layers[-1].update(
                        tail=zeros(self.cca_time0 + self.cca_time1 - 2,
                                   (self.n_heads + self.n_kv_heads) * self.head_dim),
                        prev_v=zeros(self.n_kv_heads * self.head_dim // 2))
            elif kind == "L":   # [c; kr] a slot: nothing per head
                layers.append({"latent": zeros(self.memory_len, self.kv_latent + self.qk_rope_dim)})
            else:
                layers.append({})
        # pos is float32 so the train step's observation-mask arithmetic on
        # the hidden carry (h * mask) never changes the carry dtype
        return {"layers": tuple(layers), "pos": jnp.zeros(bd, jnp.float32)}

    @nn.nowrap
    def rows_in_place(self, hidden):
        """Of a hidden tree per (row, player), the leaves that step mode
        takes whole under ``rows`` and steps one player's row of where it
        lies: a tree of bools, the SSM states' and conv tails' (``ops/ssd.py``
        ``ssd_step_rows``) and the key and value rings' (a step writes one
        slot, as an ``L`` mixer's latent ring's does) and a ``C`` mixer's
        ``tail`` and ``prev_v`` (their acting row gathered and written back by
        the mixer): all but ``pos``.  A caller
        gathers the acting player's row of every other."""
        return jax.tree_util.tree_map_with_path(
            lambda path, _: path[-1].key in (
                "ssm", "conv", "k", "v", "tail", "prev_v", "latent"), hidden)

    @nn.nowrap
    def layout(self) -> Dict[str, Any]:
        """What ``TrainContext`` records when it builds this net: the pattern,
        the passes over it and the mixer applications they make, the experts
        held of how many, and the trunk's parameters by kind."""
        d = self.d_model
        inner = self.mamba_heads * self.mamba_head_dim
        conv_dim = inner + 2 * self.n_groups * self.state_size
        norms = 2 * d if self.sandwich else d
        wide, n_e = self.router_width, self.pattern.count("E")
        latent = (self.n_heads + self.n_kv_heads) * self.head_dim
        # an ``E`` layer's router; past the first, an ``mlp`` router scales a carry
        router = {
            "sigmoid": d * self.n_experts + self.n_experts, "softmax": d * self.n_experts,
            "mlp": (d + 2) * wide + 2 * (wide + 1) * wide + (wide + 1) * self.n_experts,
        }[self.router]
        carries = max(n_e - 1, 0) * wide if self.router == "mlp" else 0
        each = {
            "M": norms + d * (inner + conv_dim + self.mamba_heads)
            + (self.conv_kernel + 1) * conv_dim + 3 * self.mamba_heads + inner + inner * d,
            # q, k, v, o; the gate's projection and the two per-head norms where it has them
            "*": norms + d * self.head_dim * ((3 if self.attn_gate else 2) * self.n_heads
                                              + 2 * self.n_kv_heads)
            + (2 * self.head_dim if self.qk_norm else 0),
            "E": norms + router + (3 if self.gated_experts else 2) * d
            * (self.shared_width + self.experts_held * self.expert_width),
            "-": norms + 3 * d * self.mlp_width,
            # q, k, the two value maps, o; the two convolutions; the temperatures
            "C": norms + d * (latent + self.n_kv_heads * self.head_dim)
            + self.n_heads * self.head_dim * d + (self.cca_time0 + 1) * latent
            + (self.cca_time1 * self.head_dim + 1) * latent + self.n_kv_heads,
            # q, the map to the latent and its norm, the map from it, o
            "L": norms + d * self.n_heads * (self.qk_nope_dim + self.qk_rope_dim)
            + (d + 1) * self.kv_latent + d * self.qk_rope_dim
            + self.n_heads * (self.kv_latent * (self.qk_nope_dim + self.v_head_dim)
                              + self.v_head_dim * d),
        }
        each["W"] = each["*"]
        return {
            "pattern": self.pattern, "loops": self.loops,
            "applications": self.loops * len(self.pattern),
            # the slots of each attention application's ring, in the hidden pytree's order
            "rings": [self.ring(kind) for kind in self.pattern * self.loops if kind in "*CLW"],
            "experts_held": self.experts_held,
            "experts": self.n_experts, "expert_offset": self.expert_offset,
            "residual_scale": self.residual_scale, "router": self.router,
            "param_dtype": self.param_dtype,
            **{f"params_{name}": sum(self.pattern.count(kind) * each[kind] for kind in kinds)
               + (carries if kinds == "E" else 0)
               for kinds, name in (("M", "mamba"), ("*W", "attention"), ("E", "experts"),
                                   ("-", "mlp"), ("C", "cca"), ("L", "latent"))},
            # of ``params_experts``, the routers' own
            "params_router": n_e * router + carries,
        }

    @nn.nowrap
    def program_scopes(self):
        """The ``jax.named_scope``s this net alone brings to a program that
        applies it, for ``utils.compile_cache.scoped_program_options``: a
        scope that came with its mixer needs a cache key of its own only
        where the mixer is (the older kinds' programs keep theirs)."""
        attends = any(kind in self.pattern for kind in "*W")
        return ((CCA_SCOPE,) if "C" in self.pattern else ()) + (
            (MLA_PROJ_SCOPE, MLA_CORE_SCOPE) if "L" in self.pattern else ()) + (
            (ATTN_PROJ_SCOPE, QK_NORM_SCOPE, ATTN_GATE_SCOPE)
            if attends and (self.qk_norm or self.attn_gate) else ())
