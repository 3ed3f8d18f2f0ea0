"""Both Pallas attention kernels, compiled for a described (not attached)
TPU v5e by the chip's own compiler, at the shapes chip_smoke.py pins.

Interpret-mode tests cannot see what the TPU compiler refuses (a slice
not aligned to the tiling, too much VMEM); these compiles can, at about
two seconds each and no chip time.  Nothing executes, so this checks that
the kernel is IN the program (``tpu_custom_call``) and that its forward
and its custom-VJP backward compile — not results, not times.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from handyrl_tpu.ops.flash_attention import flash_attention, masked_flash_attention

_NET = chip_smoke.TRANSFORMER_TPU_NET_ARGS
_STEP = chip_smoke.TRANSFORMER_TPU_OVERRIDES
_LONG = chip_smoke.TRANSFORMER_LONG_TPU
_HD = (_NET["n_heads"], _NET["d_model"] // _NET["n_heads"])

WINDOW = _NET["memory_len"]  # of every pinned transformer shape

# (B, T, H, D): the smoke's and the xfmr_train_t64 cell's step (B64 x 2
# players, T64) and the two long rows of TRANSFORMER_LONG_TPU, all d1536 /
# 16 heads -> D96, which pads to the 128-lane tile inside the kernel
PINNED = [(2 * _STEP["batch_size"], _STEP["burn_in_steps"] + _STEP["forward_steps"]) + _HD] + [
    (_LONG["batch_by_t"][t], t) + _HD for t in _LONG["sweep_t"][1:]
]
# T not a multiple of the 128 tile: only the masked kernel pads T
UNALIGNED = (8, 200, 16, 96)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip (the next run would warn and
    recompile), so the cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_fn(kernel, shape, sharding):
    """(fn(q, k, v), avals) calling ``kernel`` compiled (interpret=False —
    the auto-pick would see this process's CPU backend)."""
    B, T, H, D = shape
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    if kernel == "flash":
        return (lambda q, k, v: flash_attention(q, k, v, True, 128, 128, False)), qkv
    key_mask = jnp.ones((B, T), jnp.float32)
    slopes = 2.0 ** (-jnp.arange(1, H + 1, dtype=jnp.float32))
    return (
        lambda q, k, v: masked_flash_attention(
            q, k, v, key_mask, slopes, WINDOW, 128, 128, False
        )
    ), qkv


@pytest.mark.parametrize("mode", ["forward", "grad"])
@pytest.mark.parametrize(
    "kernel,shape",
    [(k, s) for k in ("flash", "masked") for s in PINNED] + [("masked", UNALIGNED)],
    ids=lambda v: v if isinstance(v, str) else "B%d-T%d-H%d-D%d" % v,
)
def test_kernel_compiles_for_v5e(v5e, kernel, shape, mode):
    fn, qkv = _kernel_fn(kernel, shape, v5e)
    if mode == "grad":
        fwd = fn
        fn = jax.grad(
            lambda q, k, v: (fwd(q, k, v).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2),
        )
    compiled = jax.jit(fn).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        "the Pallas kernel is not in the compiled program"
    )
