"""The jitted, sharded training step — forward, targets, loss, optimizer.

This is the TPU replacement for the reference's host-side training loop
(train.py:128-268, 348-372): ONE compiled function per batch shape doing

    forward (FF flatten or lax.scan RNN with burn-in)
    -> loss core (ops/losses.py, targets as reverse scans)
    -> global-norm clip + L2 decay + Adam
    -> parameter update

under ``jax.jit`` with NamedShardings: the batch is sharded over the 'dp'
mesh axis, params/optimizer state replicated; XLA inserts the gradient
all-reduce over ICI.  One exception: a net that sections its backward pass
(``TransformerNet``'s whole-window path) on a dp-only mesh.  There the step
differentiates under ``shard_map`` and each section rings its own gradient
round 'dp' under the next section's backward compute
(``mesh.sum_section_grads``).  The learning rate is a scalar argument (the
reference's data-count-EMA schedule, train.py:328-332/383-385, is computed
on host per epoch).

Forward-prediction semantics parity (train.py:128-187):
* feed-forward nets flatten (B, T, P) into one device batch;
* recurrent nets scan over T carrying hidden state, zeroing the carry into
  steps a player did not observe and only committing new hidden where
  observed; burn-in steps run under stop_gradient;
* policy logits are turn-masked (summed over the player axis for
  turn-alternating batches) and get the action mask subtracted;
* value-ish outputs are observation-masked (broadcasting the turn player's
  prediction against the full-player mask in turn-based mode).
"""

from __future__ import annotations


import functools
import inspect
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from ..ops import attention_core, compute_loss_from_outputs, ssd
from ..utils import tree_map
from ..utils.compile_cache import scoped_program_options
from ..utils.trace import (
    enabled as trace_enabled,
    trace_event,
    trace_phase,
    trace_phase_since,
)
from .mesh import (
    batch_sharding,
    dispatch_serialized,
    grad_sync_axis,
    grad_sync_counts,
    param_shardings,
    replicated_sharding,
    ring_order,
    sum_section_grads,
)


def _flat_apply(module, params, obs, lead_shape):
    """Apply module to observations flattened over ``lead_shape`` dims."""
    n = len(lead_shape)
    flat = tree_map(lambda x: x.reshape((-1,) + x.shape[n:]), obs)
    out = module.apply({"params": params}, flat, None)
    return {
        k: v.reshape(lead_shape + v.shape[1:])
        for k, v in out.items()
        if k != "hidden" and v is not None
    }


def _compute_dtype(args: Dict[str, Any]):
    return jnp.bfloat16 if args.get("compute_dtype") == "bfloat16" else None


def _auto_flag(args: Dict[str, Any], key: str, default: bool) -> bool:
    """Tri-state config flag: absent / None / 'auto' -> backend-chosen
    default; anything else is coerced to bool.  Without this, a literal
    ``remat: auto`` in config.yaml would be truthy and force the exact
    pathological mode the auto default exists to avoid."""
    v = args.get(key, "auto")
    if v is None or v == "auto":
        return default
    return bool(v)


def _cast_floats(tree, dtype):
    return tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def resolve_seq_attention(args: Dict[str, Any], T: int) -> str:
    """THE seq-mode attention auto-pick policy, as one shared resolver
    ('einsum' | 'flash' | 'ring' for a window of length ``T``) used by the
    compiled forward, chip_smoke.py's transformer phase, and the CI smoke —
    so "which path did the program take" is decided (and reportable) in
    exactly one place.

    ``auto`` picks the Pallas masked flash kernel for windows >=
    ``flash_min_t`` and the exact einsum below it.  The crossover is a
    property of the PROGRAM (the O(T^2) score tensor vs the kernel's fixed
    launch/block overhead; no cell holds the kernel yet, so where the
    crossover sits on today's code is not measured: ROADMAP S5).  The policy is
    shared by TPU (compiled kernel) and CPU (exact interpret-mode kernel —
    CPU long-T runs are tests/smokes on this TPU framework, and sharing
    the pick is what lets CI exercise the very program the chip compiles);
    any OTHER backend (e.g. GPU) falls back to einsum under auto, because
    the interpreter there would be a silent orders-of-magnitude slowdown
    on what may be a real training run — spell ``flash`` explicitly to
    override."""
    mode = args.get("seq_attention", "auto")
    if mode == "auto":
        if jax.default_backend() not in ("tpu", "cpu"):
            return "einsum"
        return "flash" if T >= int(args.get("flash_min_t", 128)) else "einsum"
    return mode


def resolve_seq_remat(args: Dict[str, Any], T: int) -> str:
    """The seq-path rung of the remat ladder ('none' | 'attn' | 'block').

    Explicit ladder values pass through; booleans collapse to the nearest
    rung (True -> 'block', False -> 'none'); ``auto`` turns 'block' on for
    long windows (T >= 512) on TPU, where the activations of a whole
    window would not fit beside a d1536 model's state, and stays
    'none' elsewhere (short windows fit, and the CPU path prefers speed).

    Ring attention is always 'none': each device already holds only its
    T/n shard's activations (the ring IS the memory partitioning), and
    jax.checkpoint around the shard_map ring loop tripped shard_map's
    scan-carry replication typing at trace time when the rule was written
    (not re-checked on jax 0.9.0) — the combination is rejected at config
    time and neutralized here for direct-API callers."""
    if args.get("seq_attention") == "ring":
        return "none"
    v = args.get("remat", "auto")
    if v in ("none", "attn", "block"):
        return v
    # isinstance, not identity/equality: config validation rejects bare
    # ints, and 1 == True must not silently alias a rung
    if isinstance(v, bool):
        return "block" if v else "none"
    return "block" if jax.default_backend() == "tpu" and T >= 512 else "none"


def whole_window(module, args: Dict[str, Any]) -> bool:
    """Whether ``forward_prediction`` runs a recurrent net over the whole
    window in one call (``seq=True``) and not step by step in a scan."""
    return bool(
        module.initial_state((1, 1)) is not None
        and getattr(module, "supports_seq", False)
        and args.get("seq_forward", True)
    )


def _window_call_takes(module, args: Dict[str, Any], keyword: str) -> bool:
    return whole_window(module, args) and (
        keyword in inspect.signature(module.__call__).parameters
    )


def sums_own_grads(module, args: Dict[str, Any]) -> bool:
    """Whether ``forward_prediction`` can hand the net a ``sum_grads``:
    its whole-window call does, to a net whose ``__call__`` takes one."""
    return _window_call_takes(module, args, "sum_grads")


def takes_packed_order(module, args: Dict[str, Any]) -> bool:
    """Whether ``put_batch`` makes a ``packed_order`` for the net: its
    whole-window call runs, and takes one."""
    return _window_call_takes(module, args, PACKED_ORDER)


# beside the heads, a net's whole-window call may return these two: the
# discrete choices a routed layer made (a pytree of integer arrays shaped
# like a head, the chosen indices on the last axis) and scalars it counted
CHOICES, COUNTERS = "choices", "counters"
# a batch leaf put_batch makes for a net whose whole-window call takes the
# keyword: per window part ("burn_in", "forward") an int32 (B, P, L) array,
# the index within the part of each row's i-th observed step (the part's
# length where it has none).  L, the most steps a row observes, is a shape:
# the net runs its mixers over L steps and not over the part's
PACKED_ORDER = "packed_order"
PACK_MULTIPLE = 32      # L is rounded up to this, so that few programs exist
# the ``jax.named_scope`` round the update inside ``_step``: a component of
# each of its ops' ``op_name`` in a device profile (the benchmark's
# ``update_step_share`` imports it; docs/observability.md has the naming rule)
UPDATE_SCOPE = "opt_update"


def pack_order(seen: np.ndarray, length: int) -> np.ndarray:
    """On the host: ``seen`` (..., T) bool -> (..., length) int32, the index
    of each row's i-th observed step, and ``T`` where it has none."""
    order = np.argsort(~seen, axis=-1, kind="stable")[..., :length]
    there = np.arange(length) < seen.sum(axis=-1, keepdims=True)
    return np.where(there, order, seen.shape[-1]).astype(np.int32)


def forward_prediction(module, params, batch: Dict[str, Any], args: Dict[str, Any],
                       sum_grads=None) -> Dict[str, Any]:
    """Run the net over a (B, T, P, ...) batch; returns post-burn-in outputs
    of length forward_steps, already turn/action/observation masked.

    ``sum_grads`` (only where ``sums_own_grads``; refused elsewhere, where
    no one would sum the gradient): the sum over the data-parallel chips
    that the net applies to its own parameter gradient, section by section
    inside the backward pass (``mesh.sum_section_grads``).

    With ``compute_dtype: bfloat16`` the forward runs in bf16 (params are
    cast by the caller; observations/hidden here) — MXU-rate compute with
    fp32 master weights.  Outputs are restored to fp32 before the masking
    arithmetic (the 1e32 action mask is not bf16-representable).

    A net whose whole-window call returns ``choices`` or ``counters`` gets
    them back under those keys: the choices cut to the forward steps like a
    head and otherwise untouched (with burn-in, as ``{"forward": ...,
    "window_start": ...}``: the forward steps', and the window's first
    ``forward_steps`` steps', which hold the burn-in steps'), the counters as
    they are."""
    cdt = _compute_dtype(args)
    obs = batch["observation"]
    if any(x.dtype == jnp.int8 for x in jax.tree.leaves(obs)):
        # obs_int8: host-fed batches carry int8 planes end-to-end (wire ->
        # shm -> device upload); dequantize here, inside the jitted update,
        # under the spec the generator quantized with (threaded by the
        # learner as args['_obs_quant']; absent = identity scale)
        from ..models.quantize import dequantize_obs_tree

        obs = dequantize_obs_tree(obs, args.get("_obs_quant"))
    if cdt is not None:
        # observations (and params, cast by the caller) carry bf16 through
        # the net; recurrent hidden stays fp32 — the carry must keep one
        # dtype across scan steps, and e.g. the transformer's step counter
        # is not exactly representable in bf16 past 256
        obs = _cast_floats(obs, cdt)
    B, T, P1 = batch["action"].shape[:3]
    burn_in = args["burn_in_steps"]
    hidden0 = module.initial_state((B, P1))
    seq = whole_window(module, args)
    if sum_grads is not None and not seq:
        raise ValueError("sum_grads given, but only the whole-window path takes it")

    if hidden0 is None:
        # Feed-forward compaction: put_batch may have sliced the observation
        # to the live prefix [0, T_obs) — every later step is end-of-episode
        # padding whose outputs the masks below zero exactly (make_batch
        # keeps the valid region a prefix when burn_in is 0).  Compute the
        # net only on the live steps and zero-pad the outputs back to T:
        # numerically identical, ~40% fewer forward/backward FLOPs on
        # short-episode envs like TicTacToe (reference train.py pads the
        # same windows but always pays full-T compute).
        T_obs = jax.tree.leaves(obs)[0].shape[1]
        outputs = _flat_apply(module, params, obs, (B, T_obs, P1))
        if T_obs < T:
            outputs = {
                k: jnp.pad(v, ((0, 0), (0, T - T_obs)) + ((0, 0),) * (v.ndim - 2))
                for k, v in outputs.items()
            }
        outputs = {k: v[:, burn_in:] for k, v in outputs.items()}
    elif seq:
        # whole-window attention path: one batched call instead of a T-step
        # scan — the masks reproduce the KV-cache semantics exactly (see
        # CachedSelfAttention seq mode), so values match the scan path.
        omask = batch["observation_mask"]
        assert omask.shape[2] == P1, (
            "recurrent training requires full-player batches "
            "(set observation: true for RNN models)"
        )
        to_bp = lambda x: jnp.moveaxis(x, 2, 1).reshape((B * P1, T) + x.shape[3:])
        obs_bp = tree_map(to_bp, obs)                       # (B*P, T, ...)
        km = to_bp(omask)[..., 0]                           # (B*P, T)
        # seq_attention: 'einsum' (exact O(T^2) path), 'flash' (Pallas
        # masked flash-attention kernel, blk_q/blk_k block-size knobs),
        # 'ring' (sequence-parallel masked ring attention over the mesh's
        # 'sp' axis — args['_mesh'], set by TrainContext), or 'auto'
        # (flash at T >= flash_min_t, einsum below — see
        # resolve_seq_attention, the single shared policy).  The remat
        # ladder (resolve_seq_remat: 'none'/'attn'/'block') rides the same
        # call: checkpointed blocks trade ~1 extra forward for ~n_layers x
        # less live activation HBM at long T.
        mode = resolve_seq_attention(args, T)
        ring_mesh = None
        if mode == "ring":
            # mesh shape + T divisibility are validated up front by
            # TrainContext.__init__ (fail-fast); args['_mesh'] is set there
            ring_mesh = args.get("_mesh")
        keywords = dict(
            seq=True, key_mask=km, burn_in=burn_in, use_flash=mode == "flash",
            ring_mesh=ring_mesh, remat=resolve_seq_remat(args, T),
            blk_q=int(args.get("blk_q", 128)), blk_k=int(args.get("blk_k", 128)),
            **({"sum_grads": sum_grads} if sum_grads is not None else {}),
        )
        if PACKED_ORDER in batch:   # (B, P, L) -> the net's rows
            keywords[PACKED_ORDER] = tree_map(
                lambda x: x.reshape((B * P1,) + x.shape[2:]), batch[PACKED_ORDER])
        # a net is handed the keywords its whole-window call takes
        takes = inspect.signature(module.__call__).parameters
        outs = module.apply(
            {"params": params}, obs_bp, None,
            **{k: v for k, v in keywords.items() if k in takes},
        )
        # what a net counts on the device rides beside its outputs; every
        # other leaf is rows x steps (heads, and a routed net's choices)
        counted = outs.pop(COUNTERS, None)
        to_btp = lambda v: jnp.moveaxis(v.reshape((B, P1, T) + v.shape[2:]), 1, 2)  # noqa: E731
        outputs = {
            k: tree_map(lambda v: to_btp(v)[:, burn_in:], v) for k, v in outs.items()
            if k != "hidden" and v is not None
        }
        if burn_in and CHOICES in outputs:
            # what a routed layer chose on the burn-in steps reaches the
            # forward steps through the state those steps leave, so a reader
            # of the choices gets them too: the window's first steps, in a
            # second leaf of the same shape (it overlaps the first one where
            # burn_in < forward_steps)
            outputs[CHOICES] = {
                "forward": outputs[CHOICES],
                "window_start": tree_map(lambda v: to_btp(v)[:, :T - burn_in], outs[CHOICES]),
            }
        if counted is not None:
            outputs[COUNTERS] = counted
    else:
        omask = batch["observation_mask"]
        assert omask.shape[2] == P1, (
            "recurrent training requires full-player batches "
            "(set observation: true for RNN models)"
        )
        obs_tl = tree_map(lambda x: jnp.moveaxis(x, 1, 0), obs)      # (T, B, P, ...)
        omask_tl = jnp.moveaxis(omask, 1, 0)                          # (T, B, P, 1)

        def step(hidden, x):
            obs_t, omask_t = x

            def mask_like(h):
                m = omask_t.reshape(omask_t.shape[:2] + (1,) * (h.ndim - 2))
                return m

            h_in = tree_map(lambda h: h * mask_like(h), hidden)
            h_flat = tree_map(lambda h: h.reshape((-1,) + h.shape[2:]), h_in)
            obs_flat = tree_map(lambda o: o.reshape((-1,) + o.shape[2:]), obs_t)
            out = module.apply({"params": params}, obs_flat, h_flat)
            new_hidden = tree_map(
                lambda h: h.reshape((B, P1) + h.shape[1:]), out.pop("hidden")
            )
            # commit new hidden only where observed (train.py:174)
            hidden = jax.tree.map(
                lambda h, nh: h * (1 - mask_like(h)) + nh * mask_like(nh), hidden, new_hidden
            )
            outs = {
                k: v.reshape((B, P1) + v.shape[1:]) for k, v in out.items() if v is not None
            }
            return hidden, outs

        # Backend-aware scan strategy:
        # * remat (default on TPU): recompute the body's activations in the
        #   backward pass instead of storing T steps of DRC gate tensors —
        #   ~T x less live HBM at ~1.3x forward recompute (config: remat).
        # * unroll (default on single-device CPU, i.e. the CPU-fallback
        #   train case): XLA:CPU executes ops inside while-loop
        #   bodies without its fast kernel runtime — measured 17-40x slower
        #   than the identical ops unrolled (DRC step: 9.3s looped vs 0.56s
        #   unrolled at batch 16).  Full unroll restores the fast kernels;
        #   on TPU the loop is fine and compiles T x faster, and on a
        #   multi-device mesh the unrolled body makes the SPMD partitioner's
        #   compile time explode (config: unroll).
        on_cpu = jax.default_backend() == "cpu"
        mesh = args.get("_mesh")
        one_dev = mesh is None or mesh.size == 1
        # the seq-path remat LADDER strings collapse to on/off here: the
        # scan body has no attention/FFN split to checkpoint selectively
        rv = args.get("remat", "auto")
        rv = {"none": False, "attn": True, "block": True}.get(rv, rv)
        if _auto_flag({"remat": rv}, "remat", not on_cpu):
            step = jax.checkpoint(step)
        unroll = _auto_flag(args, "unroll", on_cpu and one_dev)

        def burn_step(hidden, x):
            hidden, _ = step(hidden, x)
            return jax.lax.stop_gradient(hidden), None

        slice_t = lambda tree, lo, hi: tree_map(lambda x: x[lo:hi], tree)
        hidden = hidden0
        if burn_in > 0:
            hidden, _ = jax.lax.scan(
                burn_step, hidden,
                (slice_t(obs_tl, 0, burn_in), omask_tl[:burn_in]),
                unroll=unroll,
            )
        _, outs_tl = jax.lax.scan(
            step, hidden,
            (slice_t(obs_tl, burn_in, T), omask_tl[burn_in:]),
            unroll=unroll,
        )
        outputs = {k: jnp.moveaxis(v, 0, 1) for k, v in outs_tl.items()}  # (B, T', P, ...)

    # -- output masking (train.py:177-187), on post-burn-in arrays ---------
    tmask = batch["turn_mask"][:, burn_in:]
    omask = batch["observation_mask"][:, burn_in:]
    amask = batch["action_mask"][:, burn_in:]

    masked = {}
    for k, v in outputs.items():
        if k in (CHOICES, COUNTERS):  # a routed net's: untouched by the head masking
            masked[k] = v
            continue
        v = v.astype(jnp.float32)  # loss/target math stays fp32
        if k == "policy":
            v = v * tmask
            if v.shape[2] > 1 and P1 == 1:
                v = v.sum(axis=2, keepdims=True)  # gather the turn player's logits
            masked[k] = v - amask
        else:
            masked[k] = v * omask
    return masked


def trim_burn_in(batch: Dict[str, Any], burn_in: int) -> Dict[str, Any]:
    """Drop burn-in steps from every time-majored batch array (train.py:222).
    ``packed_order`` is the forward pass's alone and is dropped whole."""
    batch = {k: v for k, v in batch.items() if k != PACKED_ORDER}
    if burn_in == 0:
        return batch
    return {k: (v[:, burn_in:] if v.shape[1] > 1 else v) for k, v in batch.items() if k != "observation"} | {
        "observation": tree_map(lambda x: x[:, burn_in:], batch["observation"])
    }


def make_optimizer() -> optax.GradientTransformation:
    """clip(4.0) -> L2 weight decay 1e-5 -> Adam, matching reference
    train.py:328-332 + 371 (decay applied to gradients, torch-Adam style).
    The learning rate is applied separately in the train step."""
    return optax.chain(
        optax.clip_by_global_norm(4.0),
        optax.add_decayed_weights(1e-5),
        optax.scale_by_adam(),
    )


class TrainContext:
    """Owns the mesh, the optimizer, and the compiled train step."""

    def __init__(self, module, args: Dict[str, Any], mesh):
        # once a context: the mesh, the shardings, the step bound, ``model.layout``
        with trace_phase("setup.train_context", plane="learner"):
            self._build(module, args, mesh)

    def _build(self, module, args: Dict[str, Any], mesh):
        self.module = module
        # '_mesh' rides in the (untraced) args dict so forward_prediction
        # can hand the mesh to sequence-parallel attention paths
        self.args = dict(args, _mesh=mesh)
        if args.get("seq_attention") == "ring":
            sp = mesh.shape.get("sp", 1)
            if sp < 2:
                raise ValueError(
                    "seq_attention='ring' needs a mesh with an 'sp' axis of "
                    f"size >= 2 (got {dict(mesh.shape)}); set train_args.mesh "
                    "accordingly, e.g. {'dp': 2, 'sp': 4}"
                )
            T = args["burn_in_steps"] + args["forward_steps"]
            if T % sp:
                raise ValueError(
                    f"seq_attention='ring': window length {T} (burn_in_steps "
                    f"+ forward_steps) must be divisible by the 'sp' axis "
                    f"size {sp}"
                )
        # fail-fast geometry checks for the seq attention paths (same
        # construction-time-loudness contract as the ring checks above)
        if getattr(module, "supports_seq", False) and args.get("seq_forward", True):
            # same rule as config.validate_args, re-checked here for
            # direct-API callers that never pass through normalize_args —
            # the two layers must not drift into different constraints.
            # Power-of-two blocks make the padded-window divisibility of
            # ops.flash_attention.effective_blocks hold by construction
            # (the smaller power of two divides the larger).
            for name in ("blk_q", "blk_k"):
                b = int(args.get(name, 128))
                if b < 8 or (b & (b - 1)):
                    raise ValueError(
                        f"{name} must be a power of two >= 8, got {b}"
                    )
            if args.get("seq_attention") == "ring" and args.get("remat") in (
                "attn", "block", True,
            ):
                raise ValueError(
                    "remat ladder is unsupported with seq_attention='ring': "
                    "the ring already partitions activation memory over "
                    "'sp', and jax.checkpoint around the shard_map ring "
                    "loop fails shard_map's scan-carry replication typing "
                    "— set remat: none/auto"
                )
        # fail fast at construction, not mid-training in a learner thread:
        # under turn-based training, stateful models (RNN hidden or
        # KV-cache) train on all-player windows, which only exist when
        # every player's observation is recorded (the forward asserts the
        # same on batch shapes).  Simultaneous-move configs
        # (turn_based_training: false) are exempt: their single-player
        # windows observe the target player every step, so the hidden
        # carry is well-defined without the flag.
        if (
            module.initial_state((1, 1)) is not None
            and args.get("turn_based_training", True)
            and not args.get("observation")
        ):
            raise ValueError(
                "recurrent/memory models (RNN hidden or KV-cache transformer) "
                "under turn-based training require train_args.observation: "
                "true — per-step observations for every player are needed to "
                "build their all-player training windows.  (For a "
                "SINGLE-player custom env the turn player is the target "
                "player every step, so the carry is well-defined either "
                "way — set observation: true, or turn_based_training: "
                "false, to proceed.)"
            )
        if hasattr(module, "layout"):
            # a net that says how it is laid out (HybridNet: the pattern, the
            # experts held of how many) holds its experts' rows on one chip:
            # no expert exchange and no section-wise gradient sum exist yet
            if mesh.size != 1:
                raise ValueError(
                    f"{type(module).__name__} trains on mesh {{'dp': 1}} only (got "
                    f"{dict(mesh.shape)}): it has no sum_grads and its expert layers "
                    "no exchange across chips"
                )
            trace_event("model.layout", 0.0, plane="learner", **module.layout())
        self.mesh = mesh
        self.tx = make_optimizer()
        self._replicated = replicated_sharding(mesh)
        self._batch_shard = batch_sharding(mesh)
        # Feed-forward batches with burn_in 0 keep their live steps in a
        # prefix of the T axis (batch.py padding layout); put_batch then
        # slices the observation to that prefix so the train step skips
        # compute on end-of-episode padding (see forward_prediction).
        # Multi-process is excluded: every process must agree on the
        # global array shape and t_eff is computed from local rows only.
        self._ff_compact = (
            module.initial_state((1, 1)) is None
            and args.get("burn_in_steps", 0) == 0
            and args.get("compact_padding", True)
        )
        # A recurrent net whose whole-window call takes packed_order gets
        # one from put_batch (_pack): per window part, the largest bound
        # handed out so far.  It never falls, so a learner settles on one
        # program and does not flip between two
        self._packs = takes_packed_order(module, self.args)
        self._packed_bounds: Dict[str, int] = {}
        # the kernel choices (attention_core.PATHS, ssd.WINDOW_PATHS) already written out
        self._attention_paths: set = set()
        # scopes a net brings that older programs of its class lack (a
        # ``HybridNet`` with ``C`` layers: ``cca_mix``): part of the step's cache key
        self._net_scopes = tuple(getattr(module, "program_scopes", tuple)())

        loss_keys = ("p", "v", "r", "ent", "total")

        cdt = _compute_dtype(args)
        if cdt is not None and not getattr(module, "supports_seq", False):
            # bf16 on the small-conv game nets, settled by the round-4
            # dispatch-amortized on-chip profile (tools/profile_bf16.py,
            # K=32 fused, v5e, 2026-08-01): device math is PARITY — fp32
            # 3.05 ms/update vs bf16 2.93 (1.04x) at geese shapes; the
            # round-2 "2.9x slower" was a dispatch-bound measurement, not
            # kernel time.  bf16 additionally wins whenever transfers
            # dominate (smaller copies).  XLA:CPU is the real regression
            # (~0.46x: convert ops don't fuse there) — warn only there,
            # judged by the mesh that will actually run the step (a CPU
            # mesh on a TPU host still hits the CPU regression).
            if mesh.devices.flat[0].platform == "cpu":
                import sys

                print(
                    "[handyrl_tpu] compute_dtype=bfloat16 on a conv game "
                    "net under XLA:CPU: measured ~2x SLOWER than float32 "
                    "(unfused convert ops); on TPU it is parity-or-better "
                    "(see BASELINE.md bf16 row)",
                    file=sys.stderr,
                )

        # where the net sums its own gradient over 'dp', section by section
        # (a dp-only mesh, see mesh.grad_sync_axis, and a net that takes
        # sum_grads); elsewhere None, and GSPMD infers the sum.  What went
        # round the ring and what took a psum is counted when the step is
        # first traced
        sync_axis = grad_sync_axis(mesh) if sums_own_grads(module, self.args) else None
        sync_sum = None
        if sync_axis is not None:
            sync_sum = functools.partial(
                sum_section_grads, axis=sync_axis, order=ring_order(mesh)
            )
        self._sync_axis = sync_axis
        self.grad_sync: Optional[Dict[str, int]] = None

        def _loss_fn(params, batch):
            # mixed precision: bf16 copies feed the forward, fp32 master
            # params stay in the optimizer; grads come back fp32 through
            # the cast's vjp
            fwd_params = params if cdt is None else _cast_floats(params, cdt)
            if sync_axis is not None and self.grad_sync is None:
                # of the forward copy: the sum crosses the ICI in its dtype
                self.grad_sync = grad_sync_counts(fwd_params, mesh.shape[sync_axis])
                trace_event("train.grad_sync", 0.0, plane="learner", **self.grad_sync)
            outputs = forward_prediction(self.module, fwd_params, batch, self.args, sync_sum)
            outputs.pop(CHOICES, None)
            counted = outputs.pop(COUNTERS, {})
            trimmed = trim_burn_in(batch, self.args["burn_in_steps"])
            losses, dcnt = compute_loss_from_outputs(outputs, trimmed, self.args)
            full = {k: losses.get(k, jnp.zeros(())) for k in loss_keys}
            # what the net counted rides with the losses into the step's metrics
            full.update({"counter_" + k: jax.lax.stop_gradient(v) for k, v in counted.items()})
            return losses["total"], (full, dcnt)

        # Divergence sentinel (config: sentinel, default on): finite-checks
        # of the loss, the gradient global-norm, and the lr are FUSED into
        # the compiled step — the verdict rides back with the existing
        # metrics (no extra host sync on the happy path), and a bad step
        # keeps every old leaf of params and optimizer state by a select on
        # the verdict, so a single NaN/inf can never poison the params or
        # the Adam moments.  A select and not a lax.cond: a conditional is
        # a computation of its own, which fixes one layout per operand at
        # its boundary (whole-array copies of the large leaves in and out)
        # and hides the clip's norm from the one computed here (PERF.md,
        # PR 38); the select sits inside each leaf's one update fusion.
        # The host (runtime/trainer.py) counts the flags at epoch end
        # (sentinel_skipped_steps) and escalates a long bad streak to a
        # verified-checkpoint rollback.
        sentinel = bool(args.get("sentinel", True))

        _grad_fn = jax.value_and_grad(_loss_fn, has_aux=True)
        if sync_axis is not None:
            _local_grad = _grad_fn

            def _summed(params, batch):
                # each chip differentiates its own rows; the gradient
                # leaves come back already summed (inside the net's
                # backward pass), the loss sums and the data count here
                out, grads = _local_grad(params, batch)
                return jax.lax.psum(out, sync_axis), grads

            # check_vma off: the sums are spelled out above, so nothing is
            # to be inferred from (or inserted for) the replicated params
            _grad_fn = shard_map(
                _summed, mesh=mesh, in_specs=(PartitionSpec(), PartitionSpec(sync_axis)),
                out_specs=PartitionSpec(), check_vma=False,
            )

        def _step(state, batch, lr):
            (loss, (losses, dcnt)), grads = _grad_fn(state["params"], batch)
            with jax.named_scope(UPDATE_SCOPE):
                updates, opt_state = self.tx.update(grads, state["opt_state"], state["params"])
                updates = jax.tree.map(lambda u: -lr * u, updates)
                params = optax.apply_updates(state["params"], updates)
                if sentinel:
                    gnorm = optax.global_norm(grads)
                    bad = jnp.logical_not(
                        jnp.isfinite(loss) & jnp.isfinite(gnorm) & jnp.isfinite(lr)
                    )
                    # a select passes nothing from the side it drops: the
                    # update above ran on the NaN and none of it is kept
                    params, opt_state = jax.tree.map(
                        lambda old, new: jnp.where(bad, old, new),
                        (state["params"], state["opt_state"]), (params, opt_state),
                    )

            metrics = dict(losses)
            metrics["dcnt"] = dcnt
            if sentinel:
                # a skipped step contributes nothing to the epoch's loss
                # averages (a NaN loss summed once would poison them); its
                # count rides in its own key instead
                metrics = jax.tree.map(
                    lambda m: jnp.where(bad, jnp.zeros_like(m), m), metrics
                )
                metrics["sentinel_bad"] = bad.astype(jnp.float32)
            new_state = {"params": params, "opt_state": opt_state, "steps": state["steps"] + 1}
            return new_state, metrics

        # sharding follows the data: params/opt_state enter laid out by
        # init_state (replicated, or 'mp'-sharded kernels when the mesh has
        # a tensor-parallel axis), the batch enters 'dp'-sharded, and GSPMD
        # propagates — outside the dp-only shard_map above, collectives
        # fall out of the layout rather than being spelled out.  The
        # state shardings are pinned on BOTH sides of the jit (bound lazily
        # on the first state, _bind): without out_shardings the first call
        # compiles against init_state's layout, returns compiler-chosen
        # output shardings, and the second call silently recompiles — a
        # hidden ~30s stall on TPU that round 2's bench exposed.
        self._step_fn = _step

        def _steps(state, batches, lr):
            """k SGD updates under one lax.scan — one dispatch, one
            executable; metrics come back summed over the k steps (the
            trainer accumulates sums anyway).  Semantically identical to k
            separate calls with the same (held-per-epoch) lr; numerically
            equivalent only up to float reassociation, since XLA fuses the
            scan body differently than the unrolled step (pinned at
            rtol 1e-5 by tests/test_training.py)."""
            def body(s, b):
                return _step(s, b, lr)

            state, metrics = jax.lax.scan(
                body, state, batches,
                # same XLA:CPU while-loop pathology as the RNN scan above
                unroll=jax.default_backend() == "cpu" and mesh.size == 1,
            )
            return state, jax.tree.map(lambda m: m.sum(axis=0), metrics)

        self._steps_fn = _steps
        self._train_step = None
        self._train_steps = None

    def _fresh_put(self, tree):
        """Lay ``tree`` out on the mesh in NEW buffers.

        ``jax.device_put`` may alias the source buffer as one shard of the
        produced array; because the train step donates its state
        (``donate_argnums=(0,)``), an aliased layout would delete the
        caller's arrays on the first update.  A jitted identity always
        materializes fresh outputs, so the caller keeps ownership.

        The layout put is a multi-device program like any other, and this
        path also runs MID-RUN (sentinel rollback re-lays params while the
        rollout thread keeps dispatching) — so it takes the mesh's
        dispatch locks itself.  Callers must NOT wrap it again: the
        per-device locks are not reentrant."""
        shardings = param_shardings(self.mesh, tree)
        put = jax.jit(lambda t: t, out_shardings=shardings)
        return dispatch_serialized(lambda: put(tree), self.mesh)

    def _bind(self, state):
        """Compile the train step with the state layout pinned on both sides
        (in_shardings == out_shardings), so every call — including the first
        — hits one executable."""
        if self._train_step is None:
            ss = param_shardings(self.mesh, state)
            self._train_step = jax.jit(
                self._step_fn,
                donate_argnums=(0,),
                in_shardings=(ss, self._batch_shard, self._replicated),
                out_shardings=(ss, self._replicated),
                compiler_options=scoped_program_options(UPDATE_SCOPE, *self._net_scopes),
            )
        return self._train_step

    def init_state(self, params) -> Dict[str, Any]:
        # once a state (a run's first, a sentinel rollback's): float32 weights,
        # the optimizer's moments, their copies onto the mesh
        with trace_phase("setup.init_state", plane="learner"):
            return self._init_state(params)

    def _init_state(self, params) -> Dict[str, Any]:
        params = self._fresh_put(params)
        # optimizer moments inherit the params' layout (same shape-based
        # 'mp' rule, pinned so the state enters _bind's layout exactly);
        # dispatched under the mesh's locks like _fresh_put — init_state
        # runs mid-run on a sentinel rollback
        init = jax.jit(
            self.tx.init,
            out_shardings=param_shardings(
                self.mesh, jax.eval_shape(self.tx.init, params)
            ),
        )
        opt_state = dispatch_serialized(lambda: init(params), self.mesh)
        return {
            "params": params,
            "opt_state": opt_state,
            "steps": jax.device_put(jnp.zeros((), jnp.int32), self._replicated),
        }

    def put_state(self, state_host: Dict[str, Any]) -> Dict[str, Any]:
        """Lay a host-side (resumed) train state out on the mesh: every leaf
        gets the same shape-based 'mp' rule as fresh params, so a checkpoint
        written on any mesh restores onto this one."""
        return self._fresh_put(state_host)

    def _live_steps(self, batch) -> int:
        """Last T index with any turn/observation activity (+1).  Exact —
        the distinct-shape set (and so the jit cache) stays tiny in
        practice because an env's max episode length pins the batch max."""
        act = np.asarray(batch["turn_mask"]) + np.asarray(batch["observation_mask"])
        live = act.any(axis=(0, 2, 3))
        return int(live.nonzero()[0][-1]) + 1 if live.any() else 1

    def _compact_ff(self, batch, t_eff: Optional[int] = None):
        """Slice the observation to the live prefix (see _ff_compact)."""
        if not self._ff_compact or jax.process_count() > 1:
            return batch
        if t_eff is None:
            t_eff = self._live_steps(batch)
        if t_eff >= np.asarray(batch["turn_mask"]).shape[1]:
            return batch
        return dict(
            batch,
            observation=tree_map(lambda x: x[:, :t_eff], batch["observation"]),
        )

    def _pack(self, batches):
        """Give each host batch of the list its ``packed_order`` (see
        PACKED_ORDER), all at one bound per window part: the most steps any
        (row, player) of them observes there, rounded up to
        ``PACK_MULTIPLE``, at most the part's length and at least the
        largest bound handed out before.  No leaf where no bound is under
        its part's length: such a batch runs the program a batch without
        the leaf runs.  Multi-process is left alone, as in _compact_ff."""
        if not self._packs or jax.process_count() > 1:
            return batches
        seen = [np.moveaxis(np.asarray(b["observation_mask"])[..., 0] > 0, 1, 2)
                for b in batches]                                    # (B, P, T)
        burn_in = int(self.args["burn_in_steps"])
        parts = [(name, lo, hi) for name, lo, hi in (
            ("burn_in", 0, burn_in), ("forward", burn_in, seen[0].shape[-1])) if hi > lo]
        bounds = {}
        for name, lo, hi in parts:
            most = max(int(s[..., lo:hi].sum(axis=-1).max()) for s in seen)
            bound = min(hi - lo, -(-max(most, 1) // PACK_MULTIPLE) * PACK_MULTIPLE)
            bounds[name] = max(bound, self._packed_bounds.get(name, 0))
        if bounds != self._packed_bounds:   # a new program will be bound
            self._packed_bounds = bounds
            trace_event("train.packed_bound", 0.0, plane="learner", **bounds,
                        **{name + "_steps": hi - lo for name, lo, hi in parts})
        if all(bounds[name] == hi - lo for name, lo, hi in parts):
            return batches
        return [
            dict(b, **{PACKED_ORDER: {
                name: pack_order(s[..., lo:hi], bounds[name]) for name, lo, hi in parts}})
            for b, s in zip(batches, seen)]

    def put_batch(self, batch: Dict[str, Any]):
        """Lay a host batch out dp-sharded.

        Single-process: one device_put.  Multi-process (jax.distributed):
        ``batch`` is this process's LOCAL shard (global_batch /
        process_count rows); every process assembles its own shard and the
        global array is built with make_array_from_process_local_data —
        no cross-host batch traffic."""
        batch = self._compact_ff(batch)
        batch, = self._pack([batch])
        return self._put_sharded(batch, self._batch_shard, batch["action"].shape[0])

    def train_step(self, state, device_batch, lr: float):
        # concurrent multi-device programs (e.g. the sharded device
        # rollout) must reach every device in one order — see
        # mesh.dispatch_serialized
        fn = self._bind(state)
        t0, bound = time.monotonic(), fn._cache_size()
        out = dispatch_serialized(
            lambda: fn(state, device_batch, jnp.float32(lr)), self.mesh
        )
        self._first_step(fn, t0, bound)
        self._record_attention_paths()
        return out

    @staticmethod
    def _first_step(fn, t0: float, bound: int) -> None:
        """The phase ``setup.first_step``, said once the call is back: ``fn``
        holds a program more than the ``bound`` it held at ``t0``, so this
        call traced, lowered and loaded or built one (a packed bound, a live
        prefix or a ``fused_steps`` the context had not stepped: a handful a
        process) while its caller waited.  Every later call of that program
        costs the two reads."""
        if fn._cache_size() != bound:
            trace_phase_since("setup.first_step", t0, plane="learner",
                              program=getattr(fn, "__name__", "?"))

    def _record_attention_paths(self):
        """One ``model.attention_path`` event for each static choice between
        the whole-row attention kernel and the einsum lines that a trace of
        the step has made (``ops/attention_core.py`` ``fits``: per window
        part's operands), and one ``model.ssd_window_path`` for each between
        the Mamba-2 window kernel and the scan's lines (``ops/ssd.py``
        ``window_fits``), that no event of this context has said yet, once a
        tracer is on to take it."""
        made = len(attention_core.PATHS) + len(ssd.WINDOW_PATHS)
        if len(self._attention_paths) == made or not trace_enabled():
            return
        chosen = {("model.attention_path", key): made for key, made in attention_core.PATHS.items()}
        chosen.update((("model.ssd_window_path", key), made)
                      for key, made in ssd.WINDOW_PATHS.items())
        for said in set(chosen) - self._attention_paths:
            trace_event(said[0], 0.0, plane="learner", **chosen[said])
            self._attention_paths.add(said)

    def put_batches(self, host_batches):
        """Stack k host batches -> one (k, B, ...) device tree, B sharded
        over 'dp' (axis 1), for the fused train_steps path."""
        if self._ff_compact and jax.process_count() == 1:
            t_eff = max(self._live_steps(b) for b in host_batches)
            host_batches = [self._compact_ff(b, t_eff) for b in host_batches]
        host_batches = self._pack(host_batches)
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *host_batches)
        shard = NamedSharding(self.mesh, PartitionSpec(None, "dp"))
        return self._put_sharded(stacked, shard, host_batches[0]["action"].shape[0])

    def _put_sharded(self, tree, shard, B: int):
        """Lay a host tree out under ``shard``.  Single-process: one
        device_put (with a clear dp-divisibility error).  Multi-process
        (jax.distributed): ``tree`` is this process's LOCAL shard
        (global_batch / process_count rows) and the global array is built
        with make_array_from_process_local_data — no cross-host traffic."""
        if jax.process_count() > 1:
            return jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(shard, np.asarray(x)),
                tree,
            )
        dp = self.mesh.shape.get("dp", 1)
        if B % dp != 0:
            raise ValueError(f"batch size {B} not divisible by dp axis {dp}")
        return jax.device_put(tree, shard)

    def train_steps(self, state, stacked_device_batch, lr: float):
        """k fused updates (see _steps); input from put_batches."""
        if self._train_steps is None:
            ss = param_shardings(self.mesh, state)
            stacked_shard = NamedSharding(self.mesh, PartitionSpec(None, "dp"))
            self._train_steps = jax.jit(
                self._steps_fn,
                donate_argnums=(0,),
                in_shardings=(ss, stacked_shard, self._replicated),
                out_shardings=(ss, self._replicated),
                compiler_options=scoped_program_options(UPDATE_SCOPE, *self._net_scopes),
            )
        fn = self._train_steps
        t0, bound = time.monotonic(), fn._cache_size()
        out = dispatch_serialized(
            lambda: fn(state, stacked_device_batch, jnp.float32(lr)), self.mesh,
        )
        self._first_step(fn, t0, bound)
        self._record_attention_paths()
        return out

    def flops_per_step(self, state, device_batch) -> float:
        """Flops of one update (for MFU accounting): HLO cost analysis of
        the bound executable's lowering (shares the signature, so no
        second jit-cache entry), or — where the backend's cost model
        reports none — analytic counting over the jaxpr (``jaxpr_flops``:
        dot/conv terms only, which is also what dominates the HLO count)."""
        lowered = self._bind(state).lower(state, device_batch, jnp.float32(1e-5))
        flops = float((lowered.cost_analysis() or {}).get("flops", 0.0))
        if flops > 0:
            # under the shard_map the lowering holds one chip's rows of
            # the forward and backward pass
            return flops * (self.mesh.shape[self._sync_axis] if self._sync_axis else 1)
        jaxpr = jax.make_jaxpr(self._step_fn)(state, device_batch, jnp.float32(1e-5))
        return jaxpr_flops(jaxpr.jaxpr)


# peak dense bf16 FLOP/s per chip (public figures) — the denominator for
# MFU accounting (Trainer per-epoch stats -> metrics.jsonl)
PEAK_FLOPS_BY_KIND = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5", 197e12),   # v5e / v5 litepod
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _by_device_kind(table, device) -> float:
    """First-match substring lookup over a (tag, value) table; tag order
    matters (longer tags like 'v5p' before 'v5').  A device kind the
    table does not know is an error: a utilization against a guessed or
    missing peak is worse than none."""
    kind = getattr(device, "device_kind", "")
    for tag, value in table:
        if tag in kind.lower():
            return value
    raise ValueError(
        f"no peak rate on record for device kind {kind!r}; add it to the "
        "tables in handyrl_tpu/parallel/train_step.py with its source"
    )


def peak_flops_per_chip(device) -> float:
    """Peak dense bf16 FLOP/s for ``device``; raises on an unknown kind."""
    return _by_device_kind(PEAK_FLOPS_BY_KIND, device)


# peak HBM bandwidth per chip, bytes/s (public figures) — the other
# roofline axis: a step whose arithmetic intensity (flops / bytes
# accessed) sits below the ridge point peak_flops/bw is bandwidth-bound
# and its MFU ceiling is intensity * bw / peak_flops (tools/roofline.py)
HBM_BW_BY_KIND = [
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5", 819e9),    # v5e / v5 litepod
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]


def hbm_bandwidth_per_chip(device) -> float:
    return _by_device_kind(HBM_BW_BY_KIND, device)


def sub_jaxprs(eqn):
    """The jaxprs an equation holds in its params: a scan's or a while's
    body, a pjit's, a cond's branches, a custom rule's."""
    for val in eqn.params.values():
        for v in val if isinstance(val, (tuple, list)) else (val,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def jaxpr_flops(jaxpr) -> float:
    """Backend-free analytic flop count of a jaxpr: 2*MACs for every
    ``dot_general`` and ``conv_general_dilated``, recursing through
    higher-order primitives (scan multiplied by trip count, shard_map by
    the chips its body runs on, cond counted at its widest branch, while
    bodies once).  Elementwise/reduction ops
    are ignored — matmul/conv dominate the HLO count this substitutes for
    (flops_per_step's fallback, and the device-replay step's counter).  Tends to overestimate slightly (XLA simplifies some convs
    away): measured 1.15x XLA:CPU's HLO 'flops' on the GeeseNet train
    step, 1.58x on TicTacToe; factor-2 agreement is asserted by
    tests/test_training.py::test_jaxpr_flops_close_to_hlo."""
    import numpy as _np

    total = 0.0
    for eqn in jaxpr.eqns:
        p = eqn.primitive.name
        if p == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
            batch = _np.prod([lhs[i] for i in lb], dtype=float) if lb else 1.0
            contract = _np.prod([lhs[i] for i in lc], dtype=float) if lc else 1.0
            lfree = _np.prod(
                [d for i, d in enumerate(lhs) if i not in set(lc) | set(lb)],
                dtype=float,
            ) if lhs else 1.0
            rfree = _np.prod(
                [d for i, d in enumerate(rhs) if i not in set(rc) | set(rb)],
                dtype=float,
            ) if rhs else 1.0
            total += 2.0 * batch * contract * lfree * rfree
        elif p == "conv_general_dilated":
            dn = eqn.params["dimension_numbers"]
            rhs_shape = eqn.invars[1].aval.shape
            out_numel = float(_np.prod(eqn.outvars[0].aval.shape, dtype=float))
            in_feats = rhs_shape[dn.rhs_spec[1]]  # already / feature_groups
            k_spatial = _np.prod([rhs_shape[i] for i in dn.rhs_spec[2:]], dtype=float)
            total += 2.0 * out_numel * in_feats * k_spatial
        else:
            subs = list(sub_jaxprs(eqn))
            if not subs:
                continue
            if p == "scan":
                mult = float(eqn.params.get("length", 1))
                total += mult * sum(jaxpr_flops(s) for s in subs)
            elif p == "shard_map":
                # the body holds one chip's rows
                sizes = eqn.params["mesh"].shape
                mult = float(_np.prod([sizes[a] for a in eqn.params["manual_axes"]]))
                total += mult * sum(jaxpr_flops(s) for s in subs)
            elif p == "cond":
                total += max(jaxpr_flops(s) for s in subs)
            else:  # pjit, while, remat, custom_* — count bodies once
                total += sum(jaxpr_flops(s) for s in subs)
    return total
