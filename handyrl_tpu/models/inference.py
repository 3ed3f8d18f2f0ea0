"""Host-facing inference wrappers: numpy in / numpy out, jitted apply.

Replaces the reference's ModelWrapper/RandomModel (handyrl/model.py:33-74).
Key difference: ``apply`` is jitted once per (module, batch-shape) and runs
on the accelerator; hosts speak numpy pytrees at the boundary.  The
batched-across-environments path (see runtime/inference_engine.py) is the
TPU-first replacement for the reference's per-process batch-1 CPU
inference.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tree_map


@functools.lru_cache(maxsize=None)
def jitted_apply(module):
    """One compiled apply per module *value* (linen modules hash by config),
    so swapping parameters — e.g. each training epoch — never recompiles."""
    return jax.jit(module.apply)


def fetch_outputs(outputs) -> Dict[str, Any]:
    """Bring async device outputs to the host as a numpy pytree.

    The explicit fetch half of the serving plane's dispatch/fetch split:
    ``inference_batch_async`` enqueues the program (called under the
    per-device dispatch locks), and THIS runs outside them, so the locks
    cover only the enqueue — a second model's engine on the same device
    can dispatch while the first batch's outputs stream back.
    """
    return tree_map(np.asarray, jax.device_get(outputs))


def init_variables(module, env, seed: int = 0):
    """Initialize model variables from a sample observation of ``env``."""
    env.reset()
    obs = env.observation(env.players()[0])
    obs_b = tree_map(lambda x: jnp.asarray(x)[None], obs)
    hidden = module.initial_state((1,))
    return module.init(jax.random.PRNGKey(seed), obs_b, hidden)


class SingleInferenceMixin:
    """Single-sample ``inference`` on top of a batched ``inference_batch``:
    add the leading batch axis, run, strip it again (model.py:50-60)."""

    def inference(self, obs, hidden=None) -> Dict[str, Any]:
        obs_b = tree_map(lambda x: np.asarray(x)[None], obs)
        hidden_b = tree_map(lambda x: np.asarray(x)[None], hidden) if hidden is not None else None
        outputs = self.inference_batch(obs_b, hidden_b)
        return tree_map(lambda x: x[0], outputs)


class InferenceModel(SingleInferenceMixin):
    """A (module, variables) pair exposing batched and single inference.

    API kept compatible with the reference wrapper (model.py:50-60):
    ``inference(obs, hidden)`` is single-sample numpy->numpy;
    ``inference_batch`` takes/returns batch-leading pytrees.
    """

    def __init__(self, module, variables):
        self.module = module
        self.variables = variables

    @property
    def _apply(self):
        return jitted_apply(self.module)

    def init_hidden(self, batch_dims=()):
        hidden = self.module.initial_state(tuple(batch_dims))
        return None if hidden is None else tree_map(np.asarray, hidden)

    def inference_batch_async(self, obs, hidden=None):
        """Enqueue one batched apply and return the ASYNC device outputs
        (no host sync).  Callers that need numpy pass the result through
        ``fetch_outputs`` — the serving plane dispatches this under
        ``dispatch_serialized`` and fetches outside the device locks."""
        return self._apply(self.variables, obs, hidden)

    def inference_batch(self, obs, hidden=None) -> Dict[str, Any]:
        outputs = self._apply(self.variables, obs, hidden)
        return jax.device_get(outputs)


def build_inference_model(module, params, weight_dtype: str = "float32"):
    """THE engine-build seam for ``serving.weight_dtype``: every place
    that wraps a published/loaded param tree into an engine model
    (ModelRouter.publish, its cold-resolve path) goes through here, so the int8 rung reaches the serving
    plane, the fleet replicas, and the frozen league opponents from one
    switch.  Lazy import keeps the fp32 path free of the quantize
    module."""
    if weight_dtype == "int8":
        from .quantize import QuantizedInferenceModel

        return QuantizedInferenceModel(module, {"params": params})
    if weight_dtype not in (None, "float32"):
        raise ValueError(
            f"weight_dtype must be 'float32' or 'int8', got {weight_dtype!r}"
        )
    return InferenceModel(module, {"params": params})


class RandomModel:
    """Zero-logit stand-in (uniform policy over legal actions, zero value).

    Role of reference RandomModel (model.py:65-74): served as model_id 0 so
    early evaluation opponents are well-defined.
    """

    def __init__(self, output_spec: Dict[str, Any]):
        self._outputs = {
            k: np.zeros(shape, dtype) for k, (shape, dtype) in output_spec.items() if k != "hidden"
        }

    @classmethod
    def from_model(cls, model: InferenceModel, obs) -> "RandomModel":
        out = model.inference(obs, model.init_hidden())
        spec = {
            k: (v.shape, v.dtype)
            for k, v in out.items()
            if k != "hidden" and v is not None
        }
        return cls(spec)

    def init_hidden(self, batch_dims=()):
        return None

    def inference(self, obs, hidden=None, **kwargs):
        return {k: v.copy() for k, v in self._outputs.items()}
