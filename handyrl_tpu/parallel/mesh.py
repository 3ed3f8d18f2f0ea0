"""Device mesh construction and sharding helpers.

The gradient/parameter plane of the framework: where the reference used
``nn.DataParallel`` over local GPUs (train.py:340-341), we lay devices out
in a named ``jax.sharding.Mesh``.  Axes:

* ``dp`` — data parallel: batches shard along axis 0, params replicated.
  XLA/GSPMD infers the gradient's sum over ``dp`` from that layout, with
  one exception: a net that runs its backward pass in sections
  (``TransformerNet``'s whole-window path), on a mesh whose only axis
  larger than 1 is ``dp``.  There the train step differentiates under
  ``shard_map`` and each section hands its gradient to
  ``sum_section_grads``: the large leaves go round a ring of ``ppermute``s
  that runs under the next section's backward compute, the small ones
  take a ``psum``.
* further axes (e.g. ``mp``) can be added through the config
  ``train_args.mesh`` dict without touching the train step: params/batch
  shardings are derived from the mesh axis names, and XLA/GSPMD inserts
  the collectives the layout implies (gradients included).

Multi-host: under ``jax.distributed`` initialization the same code spans
hosts — ``jax.devices()`` returns the global device list and XLA routes
collectives over ICI/DCN.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.trace import trace_span

# Serializes the DISPATCH of multi-device (collective-bearing) programs
# PER DEVICE.  Two SPMD programs enqueued concurrently from different host
# threads — e.g. the sharded train step and the sharded device rollout —
# can reach the devices in a different order on different devices; XLA's
# collective rendezvous then waits for a participant that is queued behind
# the other program and aborts the process ("Expected N threads to join
# ... only N-1 arrived", reproduced on the 8-device CPU mesh).  Holding
# every participating device's lock across the enqueue (the jitted call
# returns right after dispatch; execution stays async) gives every device
# the same program order, which is the documented requirement for
# concurrent collective programs.
#
# The locks are PER DEVICE (not one global lock) so programs on DISJOINT
# device sets — the split actor/learner planes — dispatch concurrently:
# they share no device, hence no queue whose order could diverge and no
# rendezvous either could join.  Overlapping sets share at least one
# device lock and therefore serialize exactly as before; acquiring in
# global sorted id order makes the multi-lock acquisition deadlock-free.
_DEVICE_LOCKS: dict = {}
_REGISTRY_LOCK = threading.Lock()


def _locks_for(devices):
    """The per-device locks covering ``devices``, in canonical order."""
    keys = sorted({(d.process_index, d.id) for d in devices})
    with _REGISTRY_LOCK:
        return [_DEVICE_LOCKS.setdefault(k, threading.Lock()) for k in keys]


def dispatch_serialized(call, devices=None):
    """Run ``call`` (which enqueues one multi-device program and returns
    its async outputs) holding the dispatch lock of every participating
    device.

    ``devices`` names the devices the program touches: a ``Mesh``, an
    iterable of jax devices, or None for ALL local devices (the
    conservative legacy behavior — serializes with everything).  Disjoint
    device sets proceed concurrently; any overlap serializes.

    On TPU the locks cover only the enqueue — hardware per-device queues
    then preserve the program order and execution stays async.  On the
    CPU backend the locks additionally hold until the outputs are READY:
    virtual devices share one thunk pool, so a collective's rendezvous
    waiters can pin every pool thread while another in-flight program on
    an OVERLAPPING device set holds the slot the last participant needs —
    a liveness failure (XLA aborts after its 40 s rendezvous timeout)
    reproduced on the 8-device CPU mesh whenever the sharded train step
    and the sharded device rollout ran concurrently.  Disjoint-set
    programs never share a rendezvous, so holding only their own locks
    keeps them overlapping on CPU too (pinned by
    tests/test_plane.py::test_disjoint_dispatches_overlap)."""
    if devices is None:
        devices = jax.devices()
    elif isinstance(devices, Mesh):
        devices = devices.devices.flat
    locks = _locks_for(devices)
    held = []
    try:
        # acquisition inside the try: an async exception (Ctrl-C) landing
        # mid-loop must release the locks already held, or every later
        # dispatch touching those devices deadlocks.  The spans (trace:
        # enabled only — disabled is one attribute check and a shared
        # no-op context) split lock contention from program time: on CPU
        # "dispatch.run" includes execution (the lock covers readiness),
        # on TPU it is enqueue time only
        with trace_span("dispatch.wait", devices=len(locks)):
            for lock in locks:
                lock.acquire()
                held.append(lock)
        with trace_span("dispatch.run", devices=len(locks)):
            out = call()
            if jax.default_backend() == "cpu":
                jax.block_until_ready(out)
        return out
    finally:
        for lock in reversed(held):
            lock.release()


def make_mesh(spec: Optional[Dict[str, int]] = None, devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh from an axis-name -> size dict; -1 fills remaining devices.

    make_mesh({'dp': -1})            # all devices data-parallel
    make_mesh({'dp': 4, 'mp': 2})    # 4x2 two-axis mesh
    make_mesh({'dp': 2})             # sub-mesh on the first 2 devices

    All-positive axis sizes may cover a prefix of the devices (sub-mesh,
    e.g. to pin the learner to some chips); -1 axes fill what remains.
    """
    devices = list(devices if devices is not None else jax.devices())
    spec = dict(spec or {"dp": -1})
    n = len(devices)
    fixed = math.prod(s for s in spec.values() if s > 0)
    if any(s <= 0 for s in spec.values()):
        if n % max(fixed, 1) != 0:
            raise ValueError(f"{n} devices not divisible by fixed mesh axes {spec}")
        fill = n // fixed
        sizes = tuple(s if s > 0 else fill for s in spec.values())
    else:
        sizes = tuple(spec.values())
    if math.prod(sizes) > n:
        raise ValueError(f"mesh {dict(zip(spec, sizes))} needs more than {n} devices")
    return Mesh(np.asarray(devices[: math.prod(sizes)]).reshape(sizes), tuple(spec.keys()))


def split_mesh(spec: Optional[Dict[str, int]] = None, actor_chips: int = 1,
               devices: Optional[Sequence] = None):
    """Partition the device list into disjoint (learner_mesh, actor_mesh).

    The learner plane keeps the PREFIX of the device list (so device 0 —
    the coordinator / checkpoint owner — stays a learner chip) laid out by
    ``spec`` exactly as ``make_mesh`` would over that many devices; the
    actor plane takes the trailing ``actor_chips`` devices as a flat
    ``{'dp': actor_chips}`` mesh.  With per-device dispatch locks the two
    planes enqueue programs concurrently — self-play and training at full
    duty on their own chips (config: ``plane: split`` + ``actor_chips``).

    Under a multi-process ``jax.distributed`` run (``devices`` left None
    and ``jax.process_count() > 1``) the carve is per HOST, not per list
    position: every process contributes its leading ``local - actor_chips``
    devices to one GLOBAL learner mesh (the collective train step spans
    hosts over DCN) and keeps its trailing ``actor_chips`` devices as a
    process-LOCAL actor mesh — the actor plane's rollout/ingest programs
    are per-process by design (each host generates its own shard of
    episodes), so they must never be collective across hosts.  ``actor_
    chips`` therefore means "per host" in a pod-slice run.
    """
    actor_chips = int(actor_chips)
    if actor_chips < 1:
        raise ValueError(f"actor_chips must be >= 1, got {actor_chips}")
    if devices is None and jax.process_count() > 1:
        local = list(jax.local_devices())
        if actor_chips >= len(local):
            raise ValueError(
                f"plane: split needs at least one learner device PER HOST: "
                f"actor_chips {actor_chips} of {len(local)} local devices "
                "leaves none (actor_chips is per host in a multi-process run)"
            )
        # group the global list by owning process, preserving jax's order
        # within each group, so the learner mesh keeps the canonical
        # device order XLA expects for cross-host collectives
        by_proc: Dict[int, list] = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, []).append(d)
        counts = {len(ds) for ds in by_proc.values()}
        if len(counts) != 1:
            raise ValueError(
                f"plane: split needs the same local device count on every "
                f"host, got {sorted(counts)}"
            )
        learner_devs = [
            d for p in sorted(by_proc) for d in by_proc[p][: len(by_proc[p]) - actor_chips]
        ]
        learner = make_mesh(spec, learner_devs)
        actor = make_mesh({"dp": actor_chips}, local[len(local) - actor_chips:])
        return learner, actor
    devices = list(devices if devices is not None else jax.devices())
    if actor_chips >= len(devices):
        raise ValueError(
            f"plane: split needs at least one learner device: actor_chips "
            f"{actor_chips} of {len(devices)} devices leaves none"
        )
    learner = make_mesh(spec, devices[: len(devices) - actor_chips])
    actor = make_mesh({"dp": actor_chips}, devices[len(devices) - actor_chips:])
    return learner, actor


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard a (B, ...) pytree's leading axis over the 'dp' mesh axis."""
    return NamedSharding(mesh, PartitionSpec("dp"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


# A gradient leaf with fewer elements than this is summed by ``psum`` (XLA
# combines those into a few all-reduces); a larger one rides the ring's
# buffer (``sum_grads``).  At d1536 this sends every kernel but the scalar
# heads and the 270-row encoder round the ring (PERF.md, PR 31).
RING_MIN_ELEMENTS = 1 << 18


def grad_sync_axis(mesh: Mesh) -> Optional[str]:
    """``'dp'`` where a train step may sum its gradient over ``dp`` itself
    (``sum_section_grads`` under ``shard_map``): a mesh whose only axis
    larger than 1 is ``dp``.  None elsewhere: one device has nothing to
    sum, and with an ``mp`` or ``sp`` axis the gradient's collectives are
    not a plain sum over one axis, so GSPMD keeps inferring them from the
    layout."""
    sizes = dict(mesh.shape)
    if sizes.pop("dp", 1) > 1 and all(s == 1 for s in sizes.values()):
        return "dp"
    return None


def _rides_ring(x, n: int) -> bool:
    return x.ndim >= 2 and x.shape[0] % n == 0 and x.size >= RING_MIN_ELEMENTS


def ring_order(mesh: Mesh) -> Tuple[int, ...]:
    """The positions of a dp-only mesh in the order the gradient ring
    visits them.  Devices that say where they sit (a TPU's ``coords``) are
    walked boustrophedon, row by row and every other row backwards, so
    that each hop is one ICI link and the two ways round share none (v5e
    2x2: 0, 1, 3, 2; in mesh order two hops of four cross the diagonal,
    over links the other way round needs).  Devices that do not (the
    CPU's) keep the mesh's order."""
    devices = list(mesh.devices.flat)
    if not all(hasattr(d, "coords") for d in devices):
        return tuple(range(len(devices)))

    def snake(i):
        x, *rest = devices[i].coords
        return tuple(reversed(rest)) + (x if sum(rest) % 2 == 0 else -x,)

    return tuple(sorted(range(len(devices)), key=snake))


def _after(x, number):
    """``x``, to be had only once ``number`` is: a select on it.  (Not an
    ``optimization_barrier``: jax prunes a barrier's unused outputs, and
    with them the wait.)  ``number`` is a gradient's, finite in any step
    the sentinel lets through; where it is not, ``x`` is NaN too."""
    return jnp.where(jnp.isfinite(number), x, jnp.nan)


def _ring_sum(x, axis: str, order: Sequence[int]):
    """Sum ``x`` (one chunk per chip along its leading axis) over mesh axis
    ``axis`` round the ring ``order`` (mesh positions, see ``ring_order``),
    inside ``shard_map``: ``n - 1`` hops of ``ppermute`` + add leave the
    chip at ring position ``i`` holding the whole sum of chunk ``i + 1``,
    ``n - 1`` more hand the summed chunks round.  The second round ships
    the summed chunk itself, never a local re-sum, so every chip ends with
    the same bits.  The two halves of a chunk's rows go round opposite
    ways in step, over both directions of the links.  Each hop is an async
    collective-permute, which the TPU runs under compute that does not
    depend on it; an all-reduce holds the core."""
    n, rows = len(order), x.shape[1]
    place = np.empty(n, np.int32)
    place[np.asarray(order)] = np.arange(n)
    me = jnp.asarray(place)[jax.lax.axis_index(axis)]
    ways = ((slice(0, rows // 2), 1), (slice(rows // 2, rows), -1)) if rows > 1 else (
        (slice(0, rows), 1),)
    perms = [[(order[i], order[(i + step) % n]) for i in range(n)] for _, step in ways]

    def chunks(k):
        return [
            jax.lax.dynamic_index_in_dim(x, (me + step * k) % n, 0, keepdims=False)[part]
            for part, step in ways
        ]

    def hop(accs):
        return [jax.lax.ppermute(a, axis, perm) for a, perm in zip(accs, perms)]

    accs = chunks(0)
    for k in range(1, n):
        accs = [a + c for a, c in zip(hop(accs), chunks(-k))]
    # accs are the sums of chunk me + step; hand them round, each into its place
    out = jnp.zeros_like(x)
    for k in range(n):
        if k:
            accs = hop(accs)
        for acc, (part, step) in zip(accs, ways):
            at = ((me + step * (1 - k)) % n, part.start) + (0,) * (x.ndim - 2)
            out = jax.lax.dynamic_update_slice(out, acc[None], at)
    return out


def grad_sync_counts(tree, n: int) -> Dict[str, int]:
    """What crosses an axis of size ``n`` which way when ``sum_grads`` sums
    ``tree``'s gradient: leaves and bytes (of ``tree``'s dtypes) round the
    ring and by psum."""
    counts = {"ring_leaves": 0, "ring_bytes": 0, "psum_leaves": 0, "psum_bytes": 0}
    for x in jax.tree.leaves(tree):
        way = "ring" if _rides_ring(x, n) else "psum"
        counts[way + "_leaves"] += 1
        counts[way + "_bytes"] += x.size * x.dtype.itemsize
    return counts


def sum_grads(grads, axis: str, order: Sequence[int]):
    """Sum a tree of gradients over mesh axis ``axis`` (``order``: see
    ``ring_order``), inside ``shard_map``.  The leaves with at least two
    dimensions, a leading dimension the axis size divides and
    ``RING_MIN_ELEMENTS`` elements go round the ring in ONE chain of large
    hops: leaves of one row shape and dtype share a buffer (chunk ``i`` of
    it is every such leaf's rows ``i``, put together along the rows, so no
    leaf changes its tiling), and the next buffer's ring starts when the
    one before is done.  With one chain, the
    only work the scheduler finds to run between a hop's start and its
    done is the backward pass; a chain per leaf gives it the other chains'
    slices and adds, and it hides nothing under them.  Every other leaf
    takes one ``lax.psum``, which XLA combines."""
    n = len(order)
    leaves, treedef = jax.tree.flatten(grads)
    buffers: Dict[tuple, list] = {}
    for i, g in enumerate(leaves):
        if _rides_ring(g, n):
            buffers.setdefault((g.shape[1:], g.dtype), []).append(i)
        else:
            leaves[i] = jax.lax.psum(g, axis)
    done = None
    for members in buffers.values():
        buffer = jnp.concatenate(
            [leaves[i].reshape((n, -1) + leaves[i].shape[1:]) for i in members], axis=1
        )
        if done is not None:
            buffer = _after(buffer, done)       # the chain's link
        buffer = _ring_sum(buffer, axis, order)
        done = buffer.reshape(-1)[0]
        at = 0
        for i in members:
            rows = leaves[i].shape[0] // n
            leaves[i] = buffer[:, at:at + rows].reshape(leaves[i].shape)
            at += rows
    return jax.tree.unflatten(treedef, leaves)


def sum_section_grads(grads, x_ct, token, axis: str, order: Sequence[int]):
    """The collective part of one section's backward rule, for a net that
    runs its backward pass in sections (``models/transformer.py``
    ``_section``), inside ``shard_map``: ``sum_grads`` on the section's
    parameter cotangents ``grads``, ordered against the backward pass by a
    token.  ``token`` is the one the section nearer the loss made, and
    ``x_ct`` (this section's activation cotangent) goes on to the section
    before only with it in hand; the token returned is a number that
    exists once every kernel's sum here does.  So each section's sums have
    exactly the next section's backward pass to run under, and the
    scheduler cannot push them all behind the last one (which it does
    when nothing but the optimizer waits for them).
    Returns ``(summed grads, x_ct, token)``."""
    x_ct = _after(x_ct, token)
    summed = sum_grads(grads, axis, order)
    done = sum(
        (g.reshape(-1)[0].astype(token.dtype) for g in jax.tree.leaves(summed) if g.ndim >= 2),
        jnp.zeros_like(token),
    )
    return summed, x_ct, done


def param_shardings(mesh: Mesh, params):
    """Tensor-parallel parameter layout over the 'mp' mesh axis.

    Heuristic matching how dense/conv kernels want to split on TPU: a leaf
    with >=2 dims whose output-channel (last) axis divides the 'mp' size is
    sharded on that axis; everything else (biases, scales, small heads) is
    replicated.  Without an 'mp' axis this degenerates to full replication
    — the v1 data-parallel layout.  XLA/GSPMD inserts the collectives
    implied by the layout (all-gather on column-parallel matmuls etc.).
    """
    mp = mesh.shape.get("mp", 1)

    def shard(x):
        if mp > 1 and getattr(x, "ndim", 0) >= 2 and x.shape[-1] % mp == 0:
            return NamedSharding(mesh, PartitionSpec(*([None] * (x.ndim - 1)), "mp"))
        return NamedSharding(mesh, PartitionSpec())

    return jax.tree.map(shard, params)
