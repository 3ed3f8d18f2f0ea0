"""Low-precision fast path: int8 weight + observation quantization.

The game nets are bandwidth-bound (tools/roofline.py: arithmetic
intensity far below the chip's ridge point), so the lever is *fewer
bytes*, not fewer flops.  Two byte streams get an int8 rung here:

* **Weights (serving/fleet/league engines)** — per-channel symmetric
  int8 weight-only quantization (LLM.int8 lineage: fp32 scales, no
  zero-point).  Each quantizable kernel leaf (ndim >= 2, output channel
  on the LAST axis: Conv ``(kh, kw, in, out)``, Dense ``(in, out)``) is
  replaced in place inside ``variables['params']`` by a
  ``{'int8_q', 'int8_scale'}`` pair; biases/norm params stay fp32.  The
  engine holds the int8 tree device-resident and ``jitted_dequant_apply``
  dequantizes INSIDE the compiled program — XLA fuses the
  convert-and-scale into the consuming matmul/conv (dequantize-in-
  matmul), so HBM traffic for weights drops ~4x while the MXU still
  computes in fp32.  Win-rate parity is MEASURED, never assumed:
  tests/test_lowprec.py pits quantized vs fp32 through the league's
  ``PayoffMatrix`` ledger (a chip run's bar: |dwp| <= 0.03 over >= 400
  games).

* **Observations (wire / shm slots / device rings)** — static per-plane
  scale/zero-point from env metadata (``env.obs_int8_spec()``, default
  scale 1.0 / zero-point 0 — EXACT for the 0/1-occupancy planes that
  dominate the zoo: TicTacToe's 3, HungryGeese's 17, Geister's board +
  scalar are all 0/1-valued fp32).  Quantization happens once at episode
  finalize (runtime/generation.py), so the compressed wire blocks, the
  shm ring slots, and the device rings all carry int8; dequantize runs
  on device at the consumption seams (EpisodeObsView inside the ring
  sample programs, forward_prediction's observation entry) — zero extra
  host syncs, zero recompiles on warm buckets.

Calibration is activation-informed and honest: ``calibration_report``
replays stored episode observations through the fp32 and int8 engines
and reports the measured output deviation — the number is captured, not
derived from a weight-space bound.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tree_map
from .inference import SingleInferenceMixin

# the in-place wrapper marker: a params subtree with EXACTLY these keys
# is one quantized kernel leaf, not a module collection
QUANT_KEYS = frozenset({"int8_q", "int8_scale"})

# symmetric int8: codes -127..127 (the -128 code is unused so the range
# stays symmetric and dequantize needs no zero-point)
_QMAX = 127.0


def is_quantized_leaf(node: Any) -> bool:
    return isinstance(node, dict) and frozenset(node.keys()) == QUANT_KEYS


def _quantizable(leaf: np.ndarray) -> bool:
    """Kernels only: >= 2 dims and floating.  Biases, norm scales and
    other small 1-d leaves stay fp32 — they are a rounding error of the
    byte budget and quantizing them costs accuracy for nothing."""
    return leaf.ndim >= 2 and np.issubdtype(np.asarray(leaf).dtype, np.floating)


def quantize_leaf(w: np.ndarray) -> Dict[str, np.ndarray]:
    """Per-OUT-channel symmetric int8: scale over all-but-last axes.

    Flax kernel layout puts the output channel last (Dense ``(in, out)``,
    Conv ``(kh, kw, in, out)``), so axis=-1 is the per-channel granule.
    """
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=tuple(range(w.ndim - 1)))
    # an all-zero channel gets scale 1.0 (quantizes to zeros exactly);
    # the floor also guards subnormal-scale blowups on tiny channels
    scale = np.where(absmax > 0, absmax / _QMAX, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -_QMAX, _QMAX).astype(np.int8)
    return {"int8_q": q, "int8_scale": scale}


def dequantize_leaf(node: Dict[str, Any], xp=np):
    """Inverse of ``quantize_leaf``; ``xp=jnp`` runs traced inside jit
    (the compiled engines' dequantize-in-matmul path)."""
    q = node["int8_q"]
    scale = node["int8_scale"]
    if xp is np:
        return np.asarray(q, np.float32) * np.asarray(scale, np.float32)
    return q.astype(jnp.float32) * scale.astype(jnp.float32)


def _map_params(tree: Any, on_array, on_quant):
    """Structure-preserving walk that treats ``{'int8_q','int8_scale'}``
    dicts as LEAVES (a plain tree_map would descend into them)."""
    if is_quantized_leaf(tree):
        return on_quant(tree)
    if isinstance(tree, dict) or type(tree).__name__ == "FrozenDict":
        return {k: _map_params(v, on_array, on_quant) for k, v in tree.items()}
    return on_array(tree)


def quantize_params(params: Any) -> Any:
    """fp32 param tree -> tree with quantizable kernels wrapped int8.

    The result is a plain pytree (``jax.device_put`` / ``jit`` see the
    wrapper dicts as ordinary nested containers), so engine code that
    moves ``variables`` between devices needs no changes."""
    return _map_params(
        params,
        lambda leaf: quantize_leaf(leaf) if _quantizable(np.asarray(leaf)) else leaf,
        lambda node: node,  # already quantized: idempotent
    )


def dequantize_params(params: Any, xp=np) -> Any:
    """Quantized (or mixed) param tree -> all-fp32 tree."""
    return _map_params(
        params, lambda leaf: leaf, lambda node: dequantize_leaf(node, xp=xp)
    )


def has_quantized_leaves(params: Any) -> bool:
    found = []
    _map_params(params, lambda leaf: leaf, lambda node: found.append(node))
    return bool(found)


def param_bytes(params: Any) -> int:
    """Resident bytes of a param tree, honoring int8 wrappers — the
    numerator of the weight-bytes-shrink report."""
    total = [0]

    def _arr(leaf):
        total[0] += np.asarray(leaf).nbytes
        return leaf

    def _q(node):
        total[0] += np.asarray(node["int8_q"]).nbytes
        total[0] += np.asarray(node["int8_scale"]).nbytes
        return node

    _map_params(params, _arr, _q)
    return total[0]


@functools.lru_cache(maxsize=None)
def jitted_dequant_apply(module):
    """One compiled dequantizing apply per module *value* (linen modules
    hash by config) — the quantized twin of ``inference.jitted_apply``:
    swapping int8 param trees (hot-swap, league opponents) never
    recompiles, and flipping ``weight_dtype`` compiles each batch bucket
    at most once per dtype (pinned by the RecompileSentinel test)."""

    def _apply(variables, obs, hidden):
        deq = {
            k: (dequantize_params(v, xp=jnp) if k == "params" else v)
            for k, v in variables.items()
        }
        return module.apply(deq, obs, hidden)

    return jax.jit(_apply)


class QuantizedInferenceModel(SingleInferenceMixin):
    """``InferenceModel`` twin holding int8-resident params.

    Exposes the exact engine surface ``ContinuousBatcher`` consumes:
    ``module`` / settable ``variables`` (the batcher device_puts them) /
    ``init_hidden`` / ``inference_batch_async`` / ``inference_batch``.
    The dequantize runs inside the compiled apply, so the resident tree
    stays int8 on device and only the fused matmul/conv sees fp32.
    """

    def __init__(self, module, variables):
        self.module = module
        params = variables.get("params", variables)
        if not has_quantized_leaves(params):
            variables = dict(variables, params=quantize_params(params))
        self.variables = variables

    @property
    def _apply(self):
        return jitted_dequant_apply(self.module)

    def init_hidden(self, batch_dims=()):
        hidden = self.module.initial_state(tuple(batch_dims))
        return None if hidden is None else tree_map(np.asarray, hidden)

    def inference_batch_async(self, obs, hidden=None):
        return self._apply(self.variables, obs, hidden)

    def inference_batch(self, obs, hidden=None) -> Dict[str, Any]:
        outputs = self._apply(self.variables, obs, hidden)
        # graftlint: allow[HS001] reason=synchronous convenience entry for calibration/eval callers; the serving hot path uses inference_batch_async and gathers off-thread
        return jax.device_get(outputs)


def calibration_report(module, params, obs_batches: Sequence[Any],
                       hidden=None) -> Dict[str, float]:
    """MEASURED fp32-vs-int8 output deviation over replay observations.

    ``obs_batches``: batched obs pytrees drawn from stored episodes (the
    serving router samples them at publish time).  Returns max/mean absolute deviation per output head
    family collapsed to scalars — the honest calibration record the
    router logs, instead of a
    weight-space error bound that says nothing about the policy."""
    from .inference import InferenceModel

    fp32 = InferenceModel(module, {"params": params})
    q = QuantizedInferenceModel(module, {"params": params})
    max_dev, dev_sum, n = 0.0, 0.0, 0
    for obs in obs_batches:
        bdims = (jax.tree.leaves(obs)[0].shape[0],)
        h = hidden if hidden is not None else fp32.init_hidden(bdims)
        out_f = fp32.inference_batch(obs, h)
        out_q = q.inference_batch(obs, h)
        for key, vf in out_f.items():
            if key == "hidden" or vf is None:
                continue
            d = np.abs(np.asarray(vf, np.float32)
                       - np.asarray(out_q[key], np.float32))
            max_dev = max(max_dev, float(d.max()))
            dev_sum += float(d.sum())
            n += d.size
    return {
        "calib_batches": float(len(obs_batches)),
        "calib_max_dev": round(max_dev, 6),
        "calib_mean_dev": round(dev_sum / max(n, 1), 8),
    }


def calibration_batches_from_store(store, n: int) -> List[Any]:
    """Draw up to ``n`` recent episodes' observations from an
    ``EpisodeStore`` as batched obs pytrees — the learner wires this as
    the router's ``calibration_source`` so publish-time calibration runs
    against REAL replay data, not synthetic templates.  Stored int8 obs
    (the ``obs_int8`` wire plane) are host-dequantized under the spec the
    episode carries before being replayed through both engines."""
    from ..runtime.replay import decompress_block

    if n <= 0:
        return []
    batches: List[Any] = []
    for ep in store.snapshot()[-int(n):]:
        obs = decompress_block(ep["blocks"][0])["obs"]   # (t, P, ...) leaves
        if obs_tree_is_int8(obs):
            spec = None
            if ep.get("obs_scale") is not None:
                spec = list(zip(
                    np.asarray(ep["obs_scale"], np.float32).tolist(),
                    np.asarray(ep["obs_zero"], np.float32).tolist(),
                ))
            obs = dequantize_obs_tree(obs, spec)  # numpy in -> numpy out
        batches.append(tree_map(
            lambda x: np.asarray(x).reshape((-1,) + np.asarray(x).shape[2:]),
            obs,
        ))
    return batches


# -- observation int8 plane ---------------------------------------------------


def obs_quant_spec(env, obs=None) -> List[Tuple[float, float]]:
    """Per-leaf (scale, zero_point) for an env's observation pytree,
    aligned with ``jax.tree.flatten`` order.

    Envs with non-0/1 planes override via an ``obs_int8_spec()`` method;
    the default (1.0, 0) is EXACT for 0/1-occupancy planes and keeps the
    fp32 padding convention intact (quantized 0 dequantizes to 0.0 —
    required because make_batch/reset_out fill padding regions with
    zeros before the dequantize sees them)."""
    hook = getattr(env, "obs_int8_spec", None)
    if hook is not None:
        spec = [(float(s), float(z)) for s, z in hook()]
    else:
        if obs is None:
            env.reset()
            obs = env.observation(env.players()[0])
        spec = [(1.0, 0.0) for _ in jax.tree.leaves(obs)]
    for scale, zp in spec:
        if scale <= 0:
            raise ValueError(f"obs_int8 scale must be > 0, got {scale}")
    return spec


def quantize_obs_tree(tree: Any, spec: Optional[Sequence[Tuple[float, float]]] = None):
    """Host-side (numpy) obs quantize at episode finalize: the wire
    blocks, shm slots, and device rings all inherit the int8 leaves."""
    leaves, treedef = jax.tree.flatten(tree)
    if spec is None:
        spec = [(1.0, 0.0)] * len(leaves)
    out = []
    for leaf, (scale, zp) in zip(leaves, spec):
        x = np.asarray(leaf)
        if np.issubdtype(x.dtype, np.floating):
            q = np.clip(np.rint(x / scale) + zp, -128, 127).astype(np.int8)
            out.append(q)
        else:
            out.append(x)
    return jax.tree.unflatten(treedef, out)


def dequantize_obs_tree(tree: Any, spec: Optional[Sequence[Tuple[float, float]]] = None):
    """Device-side (traced) obs dequantize — runs INSIDE the jitted ring
    sample programs and the train step's forward, so int8 planes stream
    H2D/HBM and widen to fp32 only in registers.  Non-int8 leaves pass
    through untouched, making the call a no-op on fp32 batches."""
    leaves, treedef = jax.tree.flatten(tree)
    if spec is None:
        spec = [(1.0, 0.0)] * len(leaves)
    out = []
    for leaf, (scale, zp) in zip(leaves, spec):
        if leaf.dtype == jnp.int8:
            x = leaf.astype(jnp.float32)
            if zp:
                x = x - jnp.float32(zp)
            if scale != 1.0:
                x = x * jnp.float32(scale)
            out.append(x)
        else:
            out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def obs_tree_is_int8(tree: Any) -> bool:
    return any(
        np.asarray(leaf).dtype == np.int8 for leaf in jax.tree.leaves(tree)
    )
