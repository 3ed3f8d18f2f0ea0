"""The selective-state scan of a Mamba-2 mixer, in its two forms.

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

per head (``x_t`` a head's ``P`` channels, ``B_t``/``C_t`` its group's
``S``-wide input and output maps, ``A < 0`` a scalar per head), with the
state ``S`` (P x S) carried in float32.

* ``ssd_step`` — the recurrence itself, one step (the acting path).
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

import jax
import jax.numpy as jnp


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
