"""handyrl_tpu — a TPU-native distributed reinforcement learning framework.

A from-scratch JAX/XLA/Flax re-design with the capabilities of HandyRL
(reference: /root/reference, DeNA's HandyRL, MIT license): IMPALA-style
learner/worker self-play training for turn-based, simultaneous-move,
multi-player and imperfect-information games, with off-policy corrected
policy-gradient targets (MC / TD(lambda) / UPGO / V-Trace).

Architecture differences from the reference (TPU-first, not a port):

* Compute path is pure-functional JAX: the whole training update
  (forward, loss, target scans, optimizer) is ONE jitted function
  sharded over a ``jax.sharding.Mesh`` (data-parallel by default, with
  optional model axes), instead of torch ``nn.DataParallel``.
* Actor-side inference is batched across environments onto the TPU via
  an inference engine, instead of batch-1 per-process CPU inference.
* Game logic is pure numpy (no framework dependency in ``envs/``);
  neural nets live in ``models/`` as Flax modules.
* RL target recursions (reference handyrl/losses.py) are
  time-reversed ``jax.lax.scan``s, compiled and fused by XLA.
* Fixed-shape ``(B, T, P, ...)`` batches always (XLA-friendly), where the
  reference only pads short windows.
"""

import time as _time

_T_IMPORT = _time.monotonic()   # ``setup.import`` begins: nothing is imported above

__version__ = "0.1.0"

# the last line: the package's own import as a phase (docs/observability.md).
# ``utils`` is what every other part of the package imports first, and jax with
# it where this process had not imported it
from .utils.trace import trace_phase_since as _trace_phase_since  # noqa: E402

_trace_phase_since("setup.import", _T_IMPORT)
