"""Pallas flash-attention kernel tests (interpreter backend on CPU).

Golden-checked against the fp32 XLA reference for causal and full
attention, odd head dims (lane padding), bf16 inputs, and gradients
through the custom VJP.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.ops.flash_attention import flash_attention
from handyrl_tpu.ops.ring_attention import full_attention_reference as _reference


def _qkv(seed, B, T, H, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, T, H, D), jnp.float32).astype(dtype)
    return mk(kq), mk(kk), mk(kv)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 128, 2, 16), (1, 256, 4, 64)])
def test_flash_matches_reference(causal, shape):
    q, k, v = _qkv(0, *shape)
    out = flash_attention(q, k, v, causal)
    ref = _reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_bf16():
    q, k, v = _qkv(1, 2, 128, 2, 32, jnp.bfloat16)
    out = flash_attention(q, k, v, True)
    ref = _reference(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


def test_flash_gradients():
    q, k, v = _qkv(2, 1, 128, 2, 16)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference(q, k, v, True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_rejects_ragged_tiles():
    q, k, v = _qkv(3, 1, 100, 2, 16)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, True, 64, 64)


# -- masked production kernel (transformer seq-mode semantics) --------------

from handyrl_tpu.ops.flash_attention import (  # noqa: E402
    masked_attention_reference,
    masked_flash_attention,
)


def _masked_case(seed, B, T, H, D, observed_frac=1.0):
    q, k, v = _qkv(seed, B, T, H, D)
    km = jax.random.uniform(jax.random.PRNGKey(seed + 100), (B, T))
    key_mask = (km < observed_frac).astype(jnp.float32)
    slopes = 2.0 ** (-jnp.arange(1, H + 1, dtype=jnp.float32))
    return q, k, v, key_mask, slopes


@pytest.mark.parametrize(
    "T,window,observed_frac",
    [
        (128, 1 << 30, 1.0),   # tile-aligned, no eviction, fully observed
        (128, 8, 0.7),         # ring eviction + sparse observation masks
        (100, 16, 0.7),        # ragged T exercises the internal padding
    ],
)
def test_masked_flash_matches_reference(T, window, observed_frac):
    """The DEFAULT TPU seq-attention path (train_args.seq_attention 'auto')
    vs the exact einsum the transformer einsum branch executes."""
    q, k, v, key_mask, slopes = _masked_case(7, 2, T, 2, 16, observed_frac)
    out = masked_flash_attention(q, k, v, key_mask, slopes, window=window)
    ref = masked_attention_reference(q, k, v, key_mask, slopes, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_masked_flash_gradients():
    """Chunked-recompute custom VJP vs autodiff of the einsum reference."""
    q, k, v, key_mask, slopes = _masked_case(9, 1, 128, 2, 16, 0.8)

    def loss_flash(q, k, v):
        return (masked_flash_attention(q, k, v, key_mask, slopes, window=8) ** 2).sum()

    def loss_ref(q, k, v):
        return (masked_attention_reference(q, k, v, key_mask, slopes, window=8) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "T,window",
    [
        (512, 200),
        # the T1024 point rides the slow leg: interpret-mode kernel cost
        # grows with the tile grid, and the T512 point already exercises
        # every code path (multi-tile grid, eviction window, ragged mask)
        pytest.param(1024, 384, marks=pytest.mark.slow),
    ],
)
def test_masked_flash_long_window_golden(T, window):
    """The production long-context configuration — T512/T1024 windows,
    ragged observation masks, ALiBi slopes, a non-default eviction window
    — forward AND custom-VJP gradients vs the exact einsum reference
    (interpret-mode kernel on CPU).  This is the shape regime the
    long rows of chip_smoke.TRANSFORMER_LONG_TPU have on the chip; the golden pin here keeps the
    kernel exact where it is about to be trusted for training."""
    q, k, v, key_mask, slopes = _masked_case(13 + T % 7, 1, T, 2, 16, 0.7)

    out = masked_flash_attention(q, k, v, key_mask, slopes, window=window)
    ref = masked_attention_reference(q, k, v, key_mask, slopes, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return (
            masked_flash_attention(q, k, v, key_mask, slopes, window=window) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            masked_attention_reference(q, k, v, key_mask, slopes, window=window) ** 2
        ).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_masked_flash_custom_blocks():
    """blk_q/blk_k are config knobs now (train_args.blk_q/blk_k): a
    non-default tiling must compute the identical function, including at
    block sizes that force multi-tile grids and padded windows."""
    q, k, v, key_mask, slopes = _masked_case(21, 2, 192, 2, 16, 0.8)
    ref = masked_attention_reference(q, k, v, key_mask, slopes, window=24)
    for blk_q, blk_k in ((32, 64), (64, 32), (128, 128)):
        out = masked_flash_attention(
            q, k, v, key_mask, slopes, window=24, blk_q=blk_q, blk_k=blk_k
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5,
            err_msg=f"blk_q={blk_q} blk_k={blk_k}",
        )


def test_effective_blocks_single_source_of_truth():
    from handyrl_tpu.ops.flash_attention import effective_blocks

    assert effective_blocks(100, 128, 128) == (128, 128, 128)
    assert effective_blocks(192, 64, 32) == (64, 32, 192)
    assert effective_blocks(8, 256, 256) == (128, 128, 128)
    for T in (8, 100, 512, 1000):
        bq, bk, Tp = effective_blocks(T, 64, 128)
        assert Tp % bq == 0 and Tp % bk == 0 and Tp >= T


def test_masked_flash_bf16():
    """compute_dtype=bfloat16 sends bf16 q/k/v through the masked kernel;
    scores accumulate fp32 either way, so outputs track the fp32 einsum
    reference within bf16 rounding."""
    q, k, v, key_mask, slopes = _masked_case(11, 2, 128, 2, 16, 0.8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = masked_flash_attention(qb, kb, vb, key_mask, slopes, window=8)
    ref = masked_attention_reference(q, k, v, key_mask, slopes, window=8)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=3e-2, atol=3e-2
    )
