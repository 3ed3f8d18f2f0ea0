"""Losses, targets and the kernels.  Whole-row attention kernels for
``models/hybrid.py``'s window parts, each chosen by its ``fits`` from dtype
and shape alone (the einsum lines stay elsewhere, and a
``model.attention_path`` event says which): ``attention_core`` for ``*`` and
``C`` layers (one head width, the rotation over a whole head), ``latent_core``
for ``L`` layers (keys of ``qk_nope + qk_rope`` against values of ``v_head``, a
rotated key part all heads share; bfloat16 parts of ``ROWS_MIN`` queries or
more a row at widths of whole 128-lane tiles: float32, the burn-in part and
step mode keep ``LatentAttention``'s einsum lines).  Both build their mask
with ``attention_core._allowed`` and call Pallas through
``grouped_product._call``.  Beside them ``ssd.ssd_window``, a ``M`` layer's
window core (the chunked scan, the skip, the gate and the group norm) as one
kernel a (row, group), chosen by ``ssd.window_fits`` for bfloat16 parts of 16
steps or more (a ``model.ssd_window_path`` event says which): float32, the
burn-in part and step mode keep ``ssd_chunked`` and ``Mamba2Mixer``'s lines."""

from .targets import compute_target
from .losses import compute_loss_from_outputs
from .flash_attention import flash_attention
from .ring_attention import (
    full_attention_reference,
    masked_ring_attention_shard,
    masked_ring_self_attention,
    ring_attention_shard,
    ring_self_attention,
)

__all__ = [
    "compute_target",
    "compute_loss_from_outputs",
    "flash_attention",
    "ring_attention_shard",
    "ring_self_attention",
    "masked_ring_attention_shard",
    "masked_ring_self_attention",
    "full_attention_reference",
]
