"""Device-resident replay: self-play records -> training batches, all on device.

The streaming self-play path (runtime/device_rollout.py) still round-trips
every episode device -> host (episode assembly, EpisodeStore) -> device
(make_batch + a ~43 MB observation upload per HungryGeese update).  The
round-3 TPU capture measured that loop at 499 trained + 400 self-play
env-steps/s on one chip — bounded entirely by those transfers, not by
compute.  This module removes the host from the data path:

    build_streaming_fn records (K, B, ...)        [device, 1 dispatch]
      -> ingest() into per-lane step RING BUFFERS [device, 1 dispatch]
      -> sample() windows + assemble the train batch + SGD step(s)
                                                  [device, 1 dispatch]

The only host traffic left is scalar counters and the dispatches
themselves.  The reference has no analogue — its replay is host pickles
(train.py:271-319) because its actors are host processes; a device ring is
the design point TPU self-play makes natural.

Ring invariants (what makes exact episode bookkeeping cheap):

* Every lane writes exactly one record per game step (finished lanes
  auto-reset, so there are no gaps): the write head is ONE scalar ``g``
  (global step count) and slot ``s`` of every lane holds global step
  ``gs(s) = g-1 - ((g-1-s) mod S)``.
* Slots are therefore overwritten oldest-first, and training windows only
  ever read FORWARD (younger slots) — so invalidating just the slot being
  overwritten is exact: a still-valid window start can never reach an
  overwritten step, and a long episode simply loses its oldest window
  starts one by one.
* Episode ids ARE global start steps (``ep_start_g``), unique per lane,
  so finalizing an episode (write ``ep_end_g``, set ``valid``) is one
  masked compare per step; outcome/length/progress all derive from the
  two id rings, no outcome broadcast needed (the final record's
  ``outcome`` field is gathered from the end slot at sample time).
* Storage: a lane-step's record is ONE row of 32-bit words in ONE ring,
  ``rings["rec"]`` int32 ``(lanes, slots, W)`` (``RowFormat``, derived
  from the first record batch's leaves: each leaf flattened, ``bool`` as
  a byte, narrow elements packed four or two a word, every leaf starting
  on a word, ``W`` rounded up to a multiple of 128).  Such an array has
  one natural device layout, and both users take it as it is: an ingest
  writes its ``(lanes, K, W)`` block in place at ``g % S`` and a sampler
  gathers ``batch x T`` rows, so a dispatch's work follows what it writes
  and an update's what it reads, never lanes x slots.  The id rings
  (``ep_start_g``, ``ep_end_g``, ``valid``) stay ``(lanes, slots)``:
  they are read by whole-mask passes (eligibility once an update, the
  finalizing compare once an ingested step), for which slots minor is right.

Sampling parity with the host path (replay.py:110-140 + batch.py):
window starts are uniform over the legal ``train_start`` range
``[0, max(0, steps - forward_steps)]`` of every finished episode still
fully resident; one target player uniform per window
(``turn_based_training: false`` semantics, batch.py:62-67); the starts
are drawn by inverse CDF over the eligibility mask (``_draw_starts``: one
pass over the mask per update, not one per sample); padding past
the episode end reproduces make_batch exactly (prob 1, action-mask all
illegal, value frozen at the outcome, progress 1, episode_mask 0) —
pinned key-by-key against make_batch by tests/test_device_replay.py.
Two deliberate deviations, both MEASURED (round 5): recency bias is the
ring's finite capacity (oldest data falls out) instead of the reference's
per-episode acceptance curve (train.py:292-303), and window starts are
uniform over eligible STEPS, which weights episodes by the number of
windows they contain rather than uniformly.  Controlled comparison
(tools/ablate_sampler.py: one generation engine, one TrainContext, equal
updates and rollout cadence, seeded end-to-end, only the sampler swapped
— host EpisodeStore semantics vs these rings — HungryGeese, 300 updates,
2 seeds): late-mean win points vs random, ring − host = **−0.037 and
−0.017** (mean −0.027; host arm's own seed spread 0.016).  A small,
consistently-signed cost of ~0.02-0.04 win points at this budget —
the price of uniform-step windows + capacity recency, known and bounded
(docs/captures/sampler_ablation_2026-08-02_{0739,0756}.json).

Two window modes (checked at construction, dispatched by
``turn_based_training``):

* ``ff`` (``turn_based_training: false``) — simultaneous-move vector envs
  with a ``view_obs`` device view, feed-forward nets, ``burn_in_steps: 0``,
  one target player per window — the north-star HungryGeese configuration
  (``_sample_batch``).
* ``turn`` (``turn_based_training: true`` + ``observation: true``) — any
  vector env with a ``view_obs_all`` device view, all players kept per
  window (make_batch target_players = all), recurrent nets included:
  burn-in rows are real earlier steps of the same episode and hidden
  warms from zeros over them in the train step, so no hidden ring is
  needed — the Geister DRC flagship configuration (``_sample_batch_turn``).
"""

from __future__ import annotations

import sys
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..utils import tree_map
from ..utils.compile_cache import scoped_program_options
from ..utils.trace import trace_span

ILLEGAL = 1e32

# XLA module names of the replay's programs (``jit_<name>`` in a device
# profile; the benchmark's per-layer readers import these)
INGEST_PROGRAM = "ingest"
TRAIN_PROGRAM = "replay_train"
SAMPLE_PROGRAM = "replay_sample"
# ``jax.named_scope``s inside the sampler: components of its ops' ``op_name``
# in a device profile (the benchmark's ``sample_step_share`` imports them;
# docs/observability.md has the naming rule).  The whole of ``_sample`` as
# ``train_fn`` calls it; inside, the eligibility pass and the draw, the ring
# gathers with their unpacking, the observation rebuild and its masking.
# What is left of the first is the player pick, the masks and the returns
SAMPLE_SCOPE = "sample"
SAMPLE_DRAW_SCOPE = "sample_draw"
SAMPLE_ROWS_SCOPE = "sample_rows"
SAMPLE_OBS_SCOPE = "sample_obs"
SAMPLE_PART_SCOPES = (SAMPLE_DRAW_SCOPE, SAMPLE_ROWS_SCOPE, SAMPLE_OBS_SCOPE)

# record fields consumed positionally by the ring (everything else the
# streaming fn emits is an env compact-obs field, stored as-is)
_CONTROL = ("done",)


def _lane_sharding(mesh, tree):
    """Lane-leading arrays shard over 'dp'; scalars replicate."""

    def shard(x):
        if getattr(x, "ndim", 0) >= 1:
            return NamedSharding(mesh, PartitionSpec("dp"))
        return NamedSharding(mesh, PartitionSpec())

    return tree_map(shard, tree)


class RowField(NamedTuple):
    """One record leaf's place in a row: ``words`` 32-bit words from
    ``offset``, holding ``shape`` elements of ``dtype`` (a step's leaf,
    the lane axis dropped)."""

    shape: Tuple[int, ...]
    dtype: np.dtype
    offset: int
    words: int

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))

    @property
    def nbytes(self) -> int:
        """Bytes the leaf's elements take in the row (``bool``: one each)."""
        return self.size * self.dtype.itemsize


class RowFormat:
    """The record ring's storage format: which words of a row hold which
    leaf.  Built from one step's record spec (leaves ``(lanes, ...)``), the
    control fields left out; fields lie in sorted-name order, each on a
    word boundary, and ``width`` is their sum rounded up to 128 words."""

    def __init__(self, rec_spec: Dict[str, Any]):
        self.fields: Dict[str, RowField] = {}
        offset = 0
        for name in sorted(k for k in rec_spec if k not in _CONTROL):
            leaf = rec_spec[name]
            dtype = np.dtype(jax.dtypes.canonicalize_dtype(leaf.dtype))
            if dtype.itemsize not in (1, 2, 4):
                raise TypeError(
                    f"record field {name!r}: {dtype} does not pack into "
                    "32-bit words"
                )
            shape = tuple(leaf.shape[1:])
            words = -(-int(np.prod(shape, dtype=np.int64)) * dtype.itemsize // 4)
            self.fields[name] = RowField(shape, dtype, offset, words)
            offset += words
        self.used_bytes = sum(f.nbytes for f in self.fields.values())
        self.used_words = offset
        self.width = max(128, -(-offset // 128) * 128)

    def pack(self, rec: Dict[str, Any]):
        """Leaves ``lead + field.shape`` -> int32 ``lead + (width,)``."""
        parts = []
        for name, field in self.fields.items():
            x = jnp.asarray(rec[name])
            lead = x.shape[: x.ndim - len(field.shape)]
            parts.append(_to_words(x.reshape(lead + (-1,))))
        if self.used_words < self.width:
            parts.append(
                jnp.zeros(lead + (self.width - self.used_words,), jnp.int32))
        return jnp.concatenate(parts, axis=-1)

    def unpack(self, rows, names=None) -> Dict[str, Any]:
        """int32 ``lead + (width,)`` -> the named leaves (all by default),
        each ``lead + field.shape`` in its own dtype, bit for bit what
        ``pack`` was given."""
        lead = rows.shape[:-1]
        out = {}
        for name in self.fields if names is None else names:
            f = self.fields[name]
            words = rows[..., f.offset:f.offset + f.words]
            out[name] = _from_words(words, f.dtype, f.size).reshape(lead + f.shape)
        return out


def _to_words(x):
    """``(..., n)`` of a 1-, 2- or 4-byte dtype -> int32 ``(..., ceil(n *
    itemsize / 4))``.  Narrow elements go PLANAR: with ``m`` words, element
    ``j`` is bits ``[b * (j // m), b * (j // m + 1))`` of word ``j % m`` —
    shifts of whole ``(..., m)`` slices and one concatenate to undo, no
    array with a minor dimension of 4 (what a width-changing bitcast makes)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    bits = 8 * x.dtype.itemsize
    if bits == 32:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    per = 32 // bits
    u = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{bits}")).astype(jnp.uint32)
    n = u.shape[-1]
    m = -(-n // per)
    u = jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, m * per - n)])
    word = u[..., :m]
    for c in range(1, per):
        word = word | (u[..., c * m:(c + 1) * m] << (bits * c))
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def _from_words(words, dtype: np.dtype, n: int):
    """Inverse of ``_to_words``: the first ``n`` elements of ``dtype``."""
    stored = np.dtype(np.uint8) if dtype == np.bool_ else dtype
    bits = 8 * stored.itemsize
    if bits == 32:
        return jax.lax.bitcast_convert_type(words, stored)
    u = jax.lax.bitcast_convert_type(words, jnp.uint32)
    mask = jnp.uint32((1 << bits) - 1)
    u = jnp.concatenate(
        [(u >> (bits * c)) & mask for c in range(32 // bits)], axis=-1
    )[..., :n].astype(jnp.dtype(f"uint{bits}"))
    if dtype == np.bool_:
        return u != 0
    return jax.lax.bitcast_convert_type(u, stored)


def _write_block(ring, rows, pos):
    """Block ``rows`` (B, K, W) into slots ``(pos + j) % S`` of ``ring``
    (B, S, W), in place on a donated ring: one ``dynamic_update_slice`` a
    step, so a block that crosses the ring's end needs no second case and
    nothing reads the ring.  (On the v5e a scatter over the slot axis, or a
    read-modify-write of the block's slots, makes the compiler re-lay the
    whole ring out and back; these writes keep its natural layout.)"""
    S = ring.shape[1]
    for j in range(rows.shape[1]):
        ring = jax.lax.dynamic_update_slice(
            ring, rows[:, j:j + 1], (0, (pos + j) % S, 0))
    return ring


class DeviceReplay:
    """Per-lane device ring buffers + jitted ingest / sample-and-train.

    ``slots`` is the ring length in steps per lane.  It does NOT need to
    exceed the env's max episode length: an episode longer than the ring
    keeps its most recent ``slots`` steps sampleable (older window starts
    fall out exactly as if overwritten), because invalidation is by
    episode id and windows only ever read forward (younger slots).
    """

    def __init__(self, venv, module, args: Dict[str, Any], mesh,
                 n_lanes: int, slots: int = 1024):
        name = getattr(venv, "__name__", type(venv).__name__)
        if not hasattr(venv, "record"):
            raise ValueError(
                f"device_replay needs a vector env with compact-record "
                f"streaming hooks; {name} lacks them"
            )
        if args.get("turn_based_training", True):
            # all-player windows (make_batch target_players = all): the
            # recurrent/turn-based flagship path (Geister DRC).  Burn-in
            # warms hidden from zeros exactly like the host train step, so
            # no hidden ring is needed; window rows before the episode
            # start reproduce make_batch's pre-window padding.
            self.mode = "turn"
            if not args.get("observation", False):
                raise ValueError(
                    "device_replay with turn_based_training: true requires "
                    "observation: true (both players' views recorded; the "
                    "turn-player-gather batch layout keeps the host path)"
                )
            if not hasattr(venv, "view_obs_all"):
                raise ValueError(
                    f"device_replay (turn-based) needs {name}.view_obs_all "
                    "(device-side all-player observation reconstruction)"
                )
            min_slots = args.get("burn_in_steps", 0) + args["forward_steps"]
            if slots <= min_slots:
                raise ValueError(
                    f"device_replay_slots must exceed burn_in_steps + "
                    f"forward_steps = {min_slots}"
                )
        else:
            # single-target-player feed-forward windows (the north-star
            # HungryGeese configuration)
            self.mode = "ff"
            if not getattr(venv, "simultaneous", False):
                raise ValueError(
                    "device_replay with turn_based_training: false needs a "
                    f"simultaneous-move vector env; {name} is turn-based"
                )
            if not hasattr(venv, "view_obs"):
                raise ValueError(
                    f"device_replay needs {name}.view_obs (device-side "
                    "single-player observation reconstruction)"
                )
            if module.initial_state((1, 1)) is not None:
                raise ValueError(
                    "recurrent nets need whole-window hidden warmup — use "
                    "turn_based_training: true (all-player windows) or the "
                    "host path"
                )
            if args.get("burn_in_steps", 0) != 0:
                raise ValueError(
                    "device_replay with turn_based_training: false requires "
                    "burn_in_steps: 0"
                )
        dp = mesh.shape.get("dp", 1)
        if n_lanes % dp:
            raise ValueError(f"n_lanes {n_lanes} not divisible by dp axis {dp}")
        self.venv = venv
        self.module = module
        self.args = args
        self.mesh = mesh
        self.n_lanes = n_lanes
        self.slots = slots
        self.rings = None        # built lazily from the first record batch
        self.row_format: Optional[RowFormat] = None   # fixed with them
        self._ingest = None
        self._pending = None     # last dispatched stats (drain target)
        self._train_fns: Dict[int, Any] = {}
        self._sample_fns: Dict[int, Any] = {}
        self._sample_debug = None
        self.counters = {
            "ingests": 0, "episodes": 0, "game_steps": 0, "player_steps": 0,
            "outcome_sum": 0.0, "outcome_sq_sum": 0.0,
        }
        # deferred-stats FIFO (ingest_counted(defer=True)): device scalar
        # handles whose host fetch is postponed one dispatch so it overlaps
        # the ingest's execution instead of synchronizing on it
        self._stats_fifo: deque = deque()

    # -- ring construction --------------------------------------------------

    def _init_rings(self, rec_spec: Dict[str, Any]):
        """Allocate rings for one step's record layout (``rec_spec`` leaves
        are per-step (B, ...), the K axis already dropped) and fix the
        record ring's row format from it."""
        B, S = self.n_lanes, self.slots
        fmt = self.row_format = RowFormat(rec_spec)
        row_bytes = 4 * fmt.width
        # an operator with a tiny record sees what the 512 B row floor costs
        print(
            f"[handyrl_tpu] device replay ring: {B} lanes x {S} slots x "
            f"{fmt.width} words, {fmt.used_bytes} B of each {row_bytes} B row "
            f"used ({100 * (1 - fmt.used_bytes / row_bytes):.1f}% padding), "
            f"{B * S * row_bytes / 1e6:.1f} MB",
            file=sys.stderr,
        )

        def empty():
            return {
                "rec": jnp.zeros((B, S, fmt.width), jnp.int32),
                "ep_start_g": jnp.full((B, S), -1, jnp.int32),
                "ep_end_g": jnp.full((B, S), -1, jnp.int32),
                "valid": jnp.zeros((B, S), bool),
                "cur_start_g": jnp.zeros((B,), jnp.int32),
                "g": jnp.zeros((), jnp.int32),
            }

        sharding = _lane_sharding(self.mesh, jax.eval_shape(empty))
        from ..parallel.mesh import dispatch_serialized

        # the rings are born inside the program, in their layout: built
        # outside and put, the record ring is on the device twice.  A
        # multi-device program dispatched from the rollout thread — lock it
        # like every other dispatch (the trainer cannot be stepping yet with
        # an empty ring, but a split plane's learner mesh may be busy with
        # other programs)
        alloc = jax.jit(empty, out_shardings=sharding)
        return dispatch_serialized(alloc, self.mesh)

    # -- ingest -------------------------------------------------------------

    def _build_ingest(self, rec_sharding):
        S, fmt = self.slots, self.row_format

        def write_ids(ids, done):
            g = ids["g"]
            pos = g % S
            # (1) the step's slot takes the lane's current episode id.
            # Invalidating ONLY the overwritten slot is exact: slots are
            # overwritten oldest-first and windows read forward (younger
            # slots), so a still-valid start slot can never reach an
            # overwritten step — an episode losing its oldest slots just
            # loses those window starts
            ep_start_g = ids["ep_start_g"].at[:, pos].set(ids["cur_start_g"])
            ep_end_g = ids["ep_end_g"].at[:, pos].set(-1)
            valid = ids["valid"].at[:, pos].set(False)
            # (2) finished lanes: finalize every slot of the current episode.
            # Episode ids (global start steps) are unique per lane forever,
            # so this compare can never hit a stale slot of another episode
            mine = ep_start_g == ids["cur_start_g"][:, None]         # (B, S)
            fin = done[:, None] & mine
            return {
                "ep_start_g": ep_start_g,
                "ep_end_g": jnp.where(fin, g, ep_end_g),
                "valid": valid | fin,
                "cur_start_g": jnp.where(done, g + 1, ids["cur_start_g"]),
                "g": g + 1,
            }, None

        def ingest(rings, records):
            done = records["done"]                                    # (K, B)
            # the records: one packed row a lane-step, the block in place
            rows = fmt.pack({
                k: jnp.swapaxes(v, 0, 1)
                for k, v in records.items() if k not in _CONTROL
            })                                                        # (B, K, W)
            rec = _write_block(rings["rec"], rows, rings["g"] % S)
            # the books: the id rings and the head, a step at a time (the
            # record ring is not in this loop's carry)
            ids, _ = jax.lax.scan(
                write_ids, {k: v for k, v in rings.items() if k != "rec"}, done)
            rings = dict(ids, rec=rec)
            # counters for host bookkeeping (epoch cadence, gen stats):
            active = records["active"]                                # (K, B, P)
            n_done = done.sum(dtype=jnp.int32)
            # mean self-play outcome over finished episodes, per player
            # (zero-sum envs hover at 0 — reported for parity with
            # feed_episodes' generation stats)
            out_sum = (records["outcome"] * done[..., None]).sum(axis=(0, 1))
            stats = {
                "episodes": n_done,
                "game_steps": (active.sum(axis=2) > 0).sum(dtype=jnp.int32),
                "player_steps": active.sum(dtype=jnp.int32),
                "outcome_sum": out_sum,
                "outcome_sq_sum": (records["outcome"] ** 2 * done[..., None]).sum(),
            }
            return rings, stats

        ring_shard = _lane_sharding(self.mesh, self.rings)
        rep = NamedSharding(self.mesh, PartitionSpec())
        stats_shard = {
            "episodes": rep, "game_steps": rep, "player_steps": rep,
            "outcome_sum": rep, "outcome_sq_sum": rep,
        }
        ingest.__name__ = INGEST_PROGRAM
        return jax.jit(
            ingest,
            donate_argnums=(0,),
            in_shardings=(ring_shard, rec_sharding),
            out_shardings=(ring_shard, stats_shard),
        )

    def ingest(self, records) -> Dict[str, Any]:
        """Fold a (K, B, ...) record batch (one streaming-fn call) into the
        rings.  Returns device-scalar stats (fetch lazily/rarely).

        The ring swap happens INSIDE the dispatch locks: ingest donates
        the old ring buffers the moment it dispatches, so a concurrent
        train dispatch must never read ``self.rings`` between the two —
        both paths read/replace it under this mesh's per-device dispatch
        locks (train_fn reads it inside its locked lambda the same way).
        The contract is PER PLANE: ingest and train both run on this
        replay's mesh, so a split-plane actor mesh's rollout dispatches
        never contend with it."""
        if self.rings is None:
            spec = tree_map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), records)
            self.rings = self._init_rings(spec)
        if self._ingest is None:
            self._rec_sharding = tree_map(
                lambda x: NamedSharding(self.mesh, PartitionSpec(None, "dp")), records
            )
            self._ingest = self._build_ingest(self._rec_sharding)
        if jax.process_count() > 1:
            # multi-process jit refuses numpy args under partitioned
            # shardings even on a fully-addressable process-local mesh —
            # place host-born records (the episode-stage flush path)
            # explicitly; device-born rollout records pass through
            records = tree_map(
                lambda x, s: x if isinstance(x, jax.Array) else jax.device_put(x, s),
                records, self._rec_sharding,
            )
        from ..parallel.mesh import dispatch_serialized

        def _run():
            rings, stats = self._ingest(self.rings, records)
            self.rings = rings
            self._pending = stats
            return stats

        return dispatch_serialized(_run, self.mesh)

    def _account(self, dev_stats) -> Dict[str, Any]:
        """Host-fetch one ingest's stats and fold them into the cumulative
        counters (blocks until that ingest has executed)."""
        with trace_span("replay.stats_fetch"):
            # graftlint: allow[HS001] reason=THE deferred-fetch point: callers defer this one dispatch behind the next enqueue (ingest_counted defer=True), so it overlaps execution instead of serializing the rollout thread
            stats = tree_map(np.asarray, jax.device_get(dev_stats))
        self.counters["ingests"] += 1
        self.counters["episodes"] += int(stats["episodes"])
        self.counters["game_steps"] += int(stats["game_steps"])
        self.counters["player_steps"] += int(stats["player_steps"])
        self.counters["outcome_sum"] += float(stats["outcome_sum"].sum())
        self.counters["outcome_sq_sum"] += float(stats["outcome_sq_sum"])
        return stats

    def ingest_counted(self, records, defer: bool = False):
        """ingest + host fetch of the stats, accumulated into
        ``self.counters`` — the learner-integration path, which needs
        episode counts for epoch cadence anyway.

        ``defer=False`` fetches synchronously (one blocking scalar fetch
        per rollout-sized call — fine for prefill loops and tests).
        ``defer=True`` removes that last host round-trip from the hot
        path: the fetch of dispatch N happens only after dispatch N+1 has
        been enqueued, so it overlaps ingest N+1's execution instead of
        serializing the rollout thread on every ingest.  Returns the
        PREVIOUS dispatch's stats (None on the first call); callers drain
        the tail with ``flush_counted``.  Counter totals are identical
        either way (pinned by tests/test_device_replay.py)."""
        dev = self.ingest(records)
        if not defer:
            return self._account(dev)
        self._stats_fifo.append(dev)
        if len(self._stats_fifo) < 2:
            return None
        return self._account(self._stats_fifo.popleft())

    def flush_counted(self) -> Optional[Dict[str, float]]:
        """Fetch-and-account every deferred ingest still in flight; returns
        their aggregate (None when nothing was pending) so the caller can
        report the tail's episode counts."""
        agg: Optional[Dict[str, float]] = None
        while self._stats_fifo:
            stats = self._account(self._stats_fifo.popleft())
            if agg is None:
                agg = {
                    "episodes": 0, "game_steps": 0, "player_steps": 0,
                    "outcome_sum": 0.0, "outcome_sq_sum": 0.0,
                }
            agg["episodes"] += int(stats["episodes"])
            agg["game_steps"] += int(stats["game_steps"])
            agg["player_steps"] += int(stats["player_steps"])
            agg["outcome_sum"] += float(stats["outcome_sum"].sum())
            agg["outcome_sq_sum"] += float(stats["outcome_sq_sum"])
        return agg

    def drain(self) -> None:
        """Block on the last in-flight ingest (see StreamingDeviceRollout
        .drain: exiting the process mid-execution aborts XLA)."""
        if self._pending is not None:
            jax.block_until_ready(self._pending)

    def eligible_count(self) -> int:
        """Number of sampleable window starts (host sync — call before the
        first train step, or sparingly from a consumer waiting on warmup,
        not per step).  Reads the rings under this mesh's dispatch locks:
        a concurrent ingest donates the old ring buffers, and an eager
        read racing that swap would touch deleted arrays."""
        if self.rings is None:
            return 0
        from ..parallel.mesh import dispatch_serialized

        def _count():
            return _eligibility(
                self.rings, self.args["forward_steps"],
                self.args.get("burn_in_steps", 0),
            ).sum()

        # graftlint: allow[HS001] reason=documented host sync: warmup gate only, called before the first train step / sparingly, never per step
        return int(jax.device_get(dispatch_serialized(_count, self.mesh)))

    # -- sample + train -----------------------------------------------------

    def _sample(self, rings, key, batch_size: int):
        fn = _sample_batch_turn if self.mode == "turn" else _sample_batch
        return fn(rings, self.row_format, key, batch_size, self.venv,
                  self.args, self._sample_debug)

    def sample(self, key, batch_size: int, with_info: bool = False):
        """Eager one-off sampling (tests / inspection).  The production
        path fuses _sample into train_fn's single dispatch instead."""
        info = [] if with_info else None
        self._sample_debug = info
        try:
            batch = self._sample(self.rings, key, batch_size)
        finally:
            self._sample_debug = None
        if with_info:
            return batch, tree_map(np.asarray, info[0])
        return batch

    def sample_host(self, key, batch_size: int):
        """Sample ``batch_size`` windows and materialize them on HOST.

        The multi-process path: each process samples its LOCAL rings for
        its shard of the global batch, and the host rows re-enter the
        device world through ``TrainContext.put_batch`` — jax's
        ``make_array_from_process_local_data`` seam — so the collective
        train step sees one global batch assembled from per-host episode
        populations.  The fused ``train_fn`` cannot be used there: it
        would fuse a process-LOCAL gather into the cross-host collective
        program, and the rings live on different meshes per process.
        Jitted per batch size; rings read under the dispatch locks like
        every other ring consumer (a concurrent ingest donates the old
        buffers)."""
        if batch_size not in self._sample_fns:
            rep = NamedSharding(self.mesh, PartitionSpec())

            def fn(rings, key):
                return self._sample(rings, key, batch_size)

            fn.__name__ = SAMPLE_PROGRAM
            holder = {}

            def bound(key):
                if "fn" not in holder:
                    ring_shard = _lane_sharding(self.mesh, self.rings)
                    holder["fn"] = jax.jit(
                        fn, in_shardings=(ring_shard, rep), out_shardings=rep,
                        compiler_options=scoped_program_options(*SAMPLE_PART_SCOPES),
                    )
                from ..parallel.mesh import dispatch_serialized

                # self.rings is read INSIDE the locked lambda — see ingest
                return dispatch_serialized(
                    lambda: holder["fn"](self.rings, key), self.mesh
                )

            self._sample_fns[batch_size] = bound
        batch = self._sample_fns[batch_size](key)
        # graftlint: allow[HS001] reason=the point of this path IS host materialization: local shard rows cross to the collective mesh via make_array_from_process_local_data, which takes host buffers
        return tree_map(np.asarray, jax.device_get(batch))

    def train_fn(self, ctx, fused_steps: int = 1):
        """Jitted ``fn(state, key, lr) -> (state, metrics)`` running
        ``fused_steps`` sample+SGD updates from the CURRENT rings in ONE
        dispatch (metrics summed, matching TrainContext.train_steps).  The
        state layout is pinned on both sides like TrainContext._bind; the
        rings are read under this mesh's dispatch locks (see ingest) so a
        concurrent ingest can never hand the train step donated buffers."""
        if fused_steps in self._train_fns:
            return self._train_fns[fused_steps]
        from ..parallel.mesh import param_shardings
        from ..parallel.train_step import UPDATE_SCOPE

        B = self.args["batch_size"]
        step_fn = ctx._step_fn

        def one(state, rings, key, lr):
            with jax.named_scope(SAMPLE_SCOPE):
                batch = self._sample(rings, key, B)
            return step_fn(state, batch, lr)

        def fn(state, rings, key, lr):
            if fused_steps == 1:
                return one(state, rings, key, lr)

            def body(state, k):
                return one(state, rings, k, lr)

            state, metrics = jax.lax.scan(
                body, state, jax.random.split(key, fused_steps),
                unroll=jax.default_backend() == "cpu" and self.mesh.size == 1,
            )
            return state, jax.tree.map(lambda m: m.sum(axis=0), metrics)

        fn.__name__ = TRAIN_PROGRAM
        # state shardings are bound at first call (shapes unknown here)
        holder = {}

        def bound(state, key, lr):
            if "fn" not in holder:
                ss = param_shardings(self.mesh, state)
                ring_shard = _lane_sharding(self.mesh, self.rings)
                rep = NamedSharding(self.mesh, PartitionSpec())
                holder["fn"] = jax.jit(
                    fn,
                    donate_argnums=(0,),
                    in_shardings=(ss, ring_shard, rep, rep),
                    out_shardings=(ss, rep),
                    compiler_options=scoped_program_options(
                        SAMPLE_SCOPE, *SAMPLE_PART_SCOPES, UPDATE_SCOPE),
                )
            from ..parallel.mesh import dispatch_serialized

            # self.rings is read INSIDE the locked lambda — see ingest
            return dispatch_serialized(
                lambda: holder["fn"](state, self.rings, key, jnp.float32(lr)),
                self.mesh,
            )

        def jaxpr(state):
            """This program's jaxpr on the current rings (trace-only,
            nothing executes)."""
            return jax.make_jaxpr(fn)(
                state, self.rings, jax.random.PRNGKey(0), jnp.float32(1e-5)
            )

        def flops_per_update(state) -> float:
            """Analytic FLOPs of ONE SGD update of this program: jaxpr_flops
            over the fused body / fused_steps.  Sampling/assembly are
            gathers, not FLOPs, so this equals the plain train step's count
            — used for MFU in Trainer.stats."""
            from ..parallel.train_step import jaxpr_flops

            return jaxpr_flops(jaxpr(state).jaxpr) / fused_steps

        bound.jaxpr = jaxpr
        bound.flops_per_update = flops_per_update
        self._train_fns[fused_steps] = bound
        return bound


def _slot_gsteps(g, S: int):
    """Global step held by each slot: the latest write < g congruent to the
    slot index mod S (meaningful only where valid — guarded by callers)."""
    s = jnp.arange(S, dtype=jnp.int32)
    return g - 1 - ((g - 1 - s) % S)


def _eligibility(rings, forward_steps: int, burn_in_steps: int = 0):
    """(B, S) bool — slots that are legal window STARTS: part of a finished
    resident episode, with in-episode index inside the host sampler's
    ``train_start`` range [0, max(0, steps - forward_steps)]
    (replay.py:124).  With burn-in the window also reads BACKWARD
    min(burn_in, idx_in_ep) real steps, so those older slots must still be
    resident (>= the oldest global step the ring holds) — the one case the
    forward-only invalidation argument does not cover."""
    S = rings["valid"].shape[1]
    gs = _slot_gsteps(rings["g"], S)[None, :]              # (1, S)
    idx_in_ep = gs - rings["ep_start_g"]                   # (B, S)
    ep_len = rings["ep_end_g"] - rings["ep_start_g"] + 1
    max_start = jnp.maximum(0, ep_len - forward_steps)
    ok = rings["valid"] & (idx_in_ep <= max_start)
    if burn_in_steps:
        lookback = jnp.minimum(burn_in_steps, idx_in_ep)
        ok = ok & (gs - lookback >= rings["g"] - S)
    return ok


# per-step arrays the samplers consume positionally; everything else in the
# record is an env compact-obs field handed to the obs reconstruction hook.
# "reward"/"ret" are OPTIONAL: streaming rollouts derive a constant
# step_reward in closed form (_step_returns) and never record them, while
# host-born episodes (DeviceEpisodeStage) carry the generator's explicit
# per-step columns in the ring
_RECORD_FIELDS = ("active", "observing", "legal", "action", "prob", "value",
                  "outcome", "reward", "ret")


def _draw_starts(ok, key, batch_size: int):
    """``batch_size`` (lane, slot) pairs uniform over the True entries of the
    (B, S) mask ``ok``, with replacement, by inverse CDF: one integer
    ``r < ok.sum()`` per draw, the lane found in the per-lane counts'
    running sum, the slot in the running sum of that lane's mask row.  One
    pass over the mask and ``batch_size`` rows of prefix sums, whatever the
    ring holds (lane-sharded rings gather B counts, not a cross-chip scan).
    An all-False mask gives the in-range (0, 0); callers keep the trainer
    away until ``eligible_count`` says otherwise."""
    counts = ok.sum(axis=1, dtype=jnp.int32)               # (B,)
    ends = jnp.cumsum(counts)                              # (B,) inclusive
    total = ends[-1]
    # integers, not floor(uniform * total): float32 is inexact past 2^24
    r = jax.random.randint(key, (batch_size,), 0, jnp.maximum(total, 1))
    # "entries of a running sum <= r" is searchsorted(side="right"): it
    # skips empty lanes, and the first slot whose prefix count exceeds r
    lane = (ends[None, :] <= r[:, None]).sum(axis=1, dtype=jnp.int32)
    lane = jnp.where(total > 0, lane, 0)
    r_in_lane = r - (ends[lane] - counts[lane])
    prefix = jnp.cumsum(ok[lane], axis=1, dtype=jnp.int32)  # (N, S)
    slot = (prefix <= r_in_lane[:, None]).sum(axis=1, dtype=jnp.int32)
    return lane, jnp.where(total > 0, slot, 0)


def _draw_windows(rings, fmt: RowFormat, key, batch_size: int,
                  forward_steps: int, burn_in: int) -> Dict[str, Any]:
    """Shared window geometry for both sampling modes: draw eligible
    train_starts uniformly, derive per-row in-episode indices / liveness
    over the (burn_in + forward) window, and gather the windows' record
    rows — ONE gather of N x T rows, unpacked to the per-step arrays.  Rows
    with ``i_t < 0`` are burn-in underflow (before the episode start); rows
    with ``post`` are past the episode end."""
    S = rings["valid"].shape[1]
    T = burn_in + forward_steps

    with jax.named_scope(SAMPLE_DRAW_SCOPE):
        ok = _eligibility(rings, forward_steps, burn_in)
        lane, slot = _draw_starts(ok, key, batch_size)     # (N,) train_start

    gs0 = _slot_gsteps(rings["g"], S)[slot]                # (N,) train_start g
    ep_start = rings["ep_start_g"][lane, slot]
    ep_end = rings["ep_end_g"][lane, slot]
    idx0 = gs0 - ep_start                                  # in-episode index

    j = jnp.arange(T, dtype=jnp.int32)                     # (T,)
    i_t = idx0[:, None] - burn_in + j[None, :]             # (N, T) in-ep index
    gstep = ep_start[:, None] + i_t                        # (N, T) global step
    live_b = (i_t >= 0) & (gstep <= ep_end[:, None])       # (N, T)
    wslots = (slot[:, None] - burn_in + j[None, :]) % S    # (N, T)

    with jax.named_scope(SAMPLE_ROWS_SCOPE):
        rec = fmt.unpack(rings["rec"][lane[:, None], wslots])  # leaves (N, T, ...)
        # final outcome lives in the episode's END slot record (younger than
        # train_start, so resident whenever train_start's valid flag survives)
        end_slot = (slot + (ep_end - gs0)) % S
        outcome = fmt.unpack(rings["rec"][lane, end_slot], ("outcome",))["outcome"]
    out = {
        "lane": lane, "slot": slot, "i_t": i_t, "gstep": gstep,
        "ep_end": ep_end,
        "ep_len": (ep_end - ep_start + 1).astype(jnp.float32),
        "live_b": live_b, "live": live_b.astype(jnp.float32),
        "post": gstep > ep_end[:, None],
        "active": rec["active"].astype(jnp.float32),
        "observing": rec["observing"].astype(jnp.float32),
        "prob": rec["prob"],
        "value": rec["value"],
        "action": rec["action"],
        "legal": rec["legal"],
        "outcome": outcome,                                # (N, P)
        "compact": {
            k: v for k, v in rec.items() if k not in _RECORD_FIELDS
        },
    }
    # explicit per-step reward/return columns (host-born episodes); the
    # streaming path derives them in closed form instead (_step_returns)
    for k in ("reward", "ret"):
        if k in rec:
            out[k] = rec[k]
    return out


def _step_returns(venv, gamma: float, w: Dict[str, Any]):
    """Constant per-step reward and its discounted return-to-go on live
    rows (_streaming_episode's reverse accumulation in closed form)."""
    step_reward = float(getattr(venv, "step_reward", 0.0))
    if not step_reward:
        zeros = jnp.zeros(w["live"].shape, jnp.float32)
        return zeros, zeros
    n_t = (w["ep_end"][:, None] - w["gstep"] + 1).astype(jnp.float32)
    if gamma == 1.0:
        ret = step_reward * n_t
    else:
        ret = step_reward * (1 - gamma ** n_t) / (1 - gamma)
    return w["live"] * step_reward, w["live"] * ret


def _sample_batch(rings, fmt: RowFormat, key, batch_size: int, venv,
                  args: Dict[str, Any],
                  debug: Optional[list] = None) -> Dict[str, Any]:
    """Assemble a (batch_size, T, 1, ...) training batch from the rings —
    the device twin of replay.sample_window + batch.make_batch for the
    simultaneous / feed-forward / single-target-player configuration."""
    P = venv.num_players
    k_start, k_player = jax.random.split(key)
    w = _draw_windows(rings, fmt, k_start, batch_size, args["forward_steps"], 0)
    player = jax.random.randint(k_player, (batch_size,), 0, P)
    if debug is not None:
        debug.append({"lane": w["lane"], "slot": w["slot"], "player": player})
    live_b, live = w["live_b"], w["live"]

    def pick_player(x):                                    # (N, T, P, ...) -> (N, T)
        idx = player.reshape(-1, 1, 1)
        idx = jnp.broadcast_to(idx, (batch_size, x.shape[1], 1))
        idx = idx.reshape(idx.shape + (1,) * (x.ndim - 3))
        return jnp.take_along_axis(x, idx, axis=2)[:, :, 0]

    act_p = pick_player(w["active"])                       # (N, T)
    obs_p = pick_player(w["observing"])
    prob_p = pick_player(w["prob"])
    value_p = pick_player(w["value"])
    action_p = pick_player(w["action"])
    legal_p = pick_player(w["legal"])                      # (N, T, A)
    outcome_p = jnp.take_along_axis(w["outcome"], player[:, None], axis=1)[:, 0]

    tmask = live * act_p                                   # (N, T)
    omask = live * obs_p

    # leaves (N, T, ...): single array for the vector envs, a pytree for
    # host-born episodes whose obs is structured (DeviceEpisodeStage)
    with jax.named_scope(SAMPLE_OBS_SCOPE):
        planes = venv.view_obs(w["compact"], player)
        obs = tree_map(
            lambda x: (
                x * omask.reshape(omask.shape + (1,) * (x.ndim - 2))
            )[:, :, None],                                 # (N, T, 1, ...)
            planes,
        )

    amask = jnp.where(
        legal_p & (tmask[..., None] > 0), 0.0, ILLEGAL
    ).astype(jnp.float32)[:, :, None]                      # (N, T, 1, A)

    if "reward" in w:   # explicit per-step columns (host-born episodes)
        reward = pick_player(w["reward"]) * live
        ret = pick_player(w["ret"]) * live
    else:
        reward, ret = _step_returns(venv, args["gamma"], w)

    progress = jnp.where(
        live_b, w["i_t"].astype(jnp.float32) / w["ep_len"][:, None], 1.0
    )

    exp = lambda x: x[:, :, None, None]                    # (N, T) -> (N, T, 1, 1)
    return {
        "observation": obs,
        "selected_prob": exp(jnp.where(tmask > 0, prob_p, 1.0)),
        "value": exp(jnp.where(live_b, value_p * obs_p, outcome_p[:, None])),
        "action": exp(jnp.where(tmask > 0, action_p, 0).astype(jnp.int32)),
        "outcome": outcome_p[:, None, None, None],
        "reward": exp(reward),
        "return": exp(ret),
        "episode_mask": exp(live),
        "turn_mask": exp(tmask),
        "observation_mask": exp(omask),
        "action_mask": amask,
        "progress": progress[:, :, None],
    }


def _sample_batch_turn(rings, fmt: RowFormat, key, batch_size: int, venv,
                       args: Dict[str, Any],
                       debug: Optional[list] = None) -> Dict[str, Any]:
    """All-player window assembly — the device twin of sample_window +
    make_batch for ``turn_based_training: true`` with ``observation: true``
    (batch.py:62-93, target_players = all): actor- and target-side arrays
    both keep every player, windows span burn_in + forward_steps rows with
    the host's three padding regions (zeros/fills before the episode
    start, live data inside, outcome-frozen fills past the end).  Burn-in
    rows are REAL earlier steps of the same episode (start = max(0,
    train_start - burn_in), replay.py:125) — hidden warms from zeros over
    them under stop_gradient in the train step, so no hidden ring is
    stored."""
    burn_in = args.get("burn_in_steps", 0)
    T = burn_in + args["forward_steps"]
    P = venv.num_players

    w = _draw_windows(rings, fmt, key, batch_size, args["forward_steps"], burn_in)
    if debug is not None:
        debug.append({"lane": w["lane"], "slot": w["slot"],
                      "player": jnp.full((batch_size,), -1, jnp.int32)})
    live_b, live, outcome = w["live_b"], w["live"], w["outcome"]

    act = live[..., None] * w["active"]                    # (N, T, P)
    obsv = live[..., None] * w["observing"]

    with jax.named_scope(SAMPLE_OBS_SCOPE):
        planes = venv.view_obs_all(w["compact"])           # leaves (N, T, P, ...)
        obs = tree_map(
            lambda x: x * obsv.reshape(obsv.shape + (1,) * (x.ndim - 3)), planes
        )

    amask = jnp.where(
        w["legal"] & (act[..., None] > 0), 0.0, ILLEGAL
    ).astype(jnp.float32)                                  # (N, T, P, A)

    per_p = lambda x: jnp.broadcast_to(x[:, :, None, None], (batch_size, T, P, 1))
    if "reward" in w:   # explicit per-step columns (host-born episodes)
        reward_col = (w["reward"] * live[..., None])[..., None]  # (N, T, P, 1)
        ret_col = (w["ret"] * live[..., None])[..., None]
    else:
        reward, ret = _step_returns(venv, args["gamma"], w)
        reward_col, ret_col = per_p(reward), per_p(ret)

    # value: live rows carry the recorded estimate (x observing), rows past
    # the end freeze at the outcome, burn-in underflow rows are 0
    value_b = jnp.where(
        live_b[..., None], w["value"] * obsv,
        jnp.where(w["post"][..., None], outcome[:, None, :], 0.0),
    )

    progress = jnp.where(
        live_b, w["i_t"].astype(jnp.float32) / w["ep_len"][:, None], 1.0
    )

    return {
        "observation": obs,
        "selected_prob": jnp.where(act > 0, w["prob"], 1.0)[..., None],
        "value": value_b[..., None],
        "action": jnp.where(act > 0, w["action"], 0).astype(jnp.int32)[..., None],
        "outcome": outcome[:, None, :, None],
        "reward": reward_col,
        "return": ret_col,
        "episode_mask": live[:, :, None, None],
        "turn_mask": act[..., None],
        "observation_mask": obsv[..., None],
        "action_mask": amask,
        "progress": progress[:, :, None],
    }


# -- host-born episodes: wire blobs -> device rings ---------------------------


class EpisodeObsView:
    """venv-like shim for host-born episodes staged into device rings.

    The streaming path reconstructs observations on device from an env's
    COMPACT record fields (``venv.view_obs``); host-born episodes already
    carry their full observation planes, so those live in the ring
    verbatim (pytree leaves flattened under ``obs<i>`` keys) and
    "reconstruction" is a per-player gather.  ``simultaneous``/ff mode
    here means make_batch's non-turn-based layout — one uniform target
    player per window — which is defined for ANY env's episodes, so the
    flag is unconditionally true.  ``step_reward`` is unused: the ring
    carries the generator's explicit per-step reward/return columns.
    """

    simultaneous = True
    step_reward = 0.0

    # DeviceReplay's constructor only probes for the streaming-hook's
    # presence; the stage drives ingest with pre-built record chunks
    record = None

    def __init__(self, num_players: int, obs_treedef, n_obs_leaves: int,
                 obs_spec=None):
        self.num_players = num_players
        self._treedef = obs_treedef
        self._n = n_obs_leaves
        # obs_int8: per-leaf (scale, zero_point) the episode's obs planes
        # were quantized under (rides in the episode dict as
        # obs_scale/obs_zero); None = obs stored at native dtype
        self._spec = obs_spec

    def _tree(self, compact: Dict[str, Any]):
        tree = jax.tree.unflatten(
            self._treedef, [compact[f"obs{i}"] for i in range(self._n)]
        )
        if self._spec is not None:
            # dequantize-on-device: runs INSIDE the jitted sample/assemble
            # programs (XLA fuses convert+mul into the gather consumers),
            # so the ring stays int8-resident and the host never touches
            # float obs on this path
            from ..models.quantize import dequantize_obs_tree

            tree = dequantize_obs_tree(tree, self._spec)
        return tree

    def view_obs(self, compact: Dict[str, Any], player):
        def pick(x):                         # (N, T, P, ...) -> (N, T, ...)
            idx = player.reshape((-1, 1, 1) + (1,) * (x.ndim - 3))
            idx = jnp.broadcast_to(idx, x.shape[:2] + (1,) + x.shape[3:])
            return jnp.take_along_axis(x, idx, axis=2)[:, :, 0]

        return tree_map(pick, self._tree(compact))

    def view_obs_all(self, compact: Dict[str, Any]):
        return self._tree(compact)           # leaves (N, T, P, ...)


class DeviceEpisodeStage:
    """Host-born episodes uploaded ONCE into DeviceReplay ring buffers.

    The host-fed pipeline re-uploads every sampled observation window per
    update (~43 MB/update on HungryGeese); this stage removes the host from the per-update path for
    episodes that are BORN on the host (worker actors, remote workers):

        episode (decoded dict, or the wire-codec bytes EpisodeStore
        mirrors to batcher children)
          -> per-step record columns, queued per lane  [host, once]
          -> fixed-size (chunk, lanes) ingest calls    [one H2D per chunk]
          -> DeviceReplay rings: windows sampled + assembled ON DEVICE by
             the same programs the streaming path uses (parity pinned
             key-by-key against make_batch by tests/test_device_stage.py)

    Lane discipline: the ring invariant is that every lane advances one
    slot per global step, so episodes queue per lane (shortest queue
    first — greedy balancing) and a chunk flushes only when EVERY lane
    has ``chunk_steps`` queued.  An episode's steps therefore occupy a
    contiguous lane-local span whose indices EQUAL the ring's global
    steps, which is what makes window bookkeeping exact.  Keep
    ``n_lanes * chunk_steps`` well below ``minimum_episodes`` x the
    typical episode length, or the first flush (and the trainer's first
    batch) waits on generation.
    """

    def __init__(self, module, args: Dict[str, Any], mesh, n_lanes: int = 8,
                 slots: int = 1024, chunk_steps: int = 64,
                 track_episodes: bool = False):
        # mirror DeviceReplay's ARG-side mode checks here, eagerly: the
        # replay itself is built lazily from the first episode (it needs
        # the player count and obs structure), which happens on a feeder
        # thread — too late for make_pipeline's loud fallback
        if args.get("turn_based_training", True):
            if not args.get("observation", False):
                raise ValueError(
                    "batch_pipeline: device with turn_based_training: true "
                    "requires observation: true (all-player windows; the "
                    "turn-player-gather batch layout keeps the host path)"
                )
            min_slots = args.get("burn_in_steps", 0) + args["forward_steps"]
            if slots <= min_slots:
                raise ValueError(
                    f"device_stage_slots must exceed burn_in_steps + "
                    f"forward_steps = {min_slots}"
                )
        else:
            if module.initial_state((1, 1)) is not None:
                raise ValueError(
                    "batch_pipeline: device with a recurrent net needs "
                    "turn_based_training: true (whole-window hidden warmup)"
                )
            if args.get("burn_in_steps", 0) != 0:
                raise ValueError(
                    "batch_pipeline: device with turn_based_training: false "
                    "requires burn_in_steps: 0"
                )
        dp = mesh.shape.get("dp", 1)
        if n_lanes % dp:
            rounded = max(dp, (n_lanes + dp - 1) // dp * dp)
            import sys

            print(
                f"[handyrl_tpu] device_stage_lanes {n_lanes} rounded to "
                f"{rounded} (lanes shard over the mesh's dp axis of {dp})",
                file=sys.stderr,
            )
            n_lanes = rounded
        self.module = module
        self.args = args
        self.mesh = mesh
        self.n_lanes = n_lanes
        self.slots = slots
        self.chunk_steps = int(chunk_steps)
        self.replay: Optional[DeviceReplay] = None
        self._view: Optional[EpisodeObsView] = None
        # per-lane FIFO of [rec_dict, offset] with (T, ...) numpy leaves
        self._queues: List[List[list]] = [[] for _ in range(n_lanes)]
        self._qlen = [0] * n_lanes     # pending (unflushed) steps
        self._qtotal = [0] * n_lanes   # steps EVER enqueued = ring g of the
        #                                lane's next step once flushed
        self.episodes_staged = 0
        self.steps_staged = 0
        self.chunks_flushed = 0
        # (g0, g1, episode) spans per lane — test/debug bookkeeping only
        # (unbounded over a long run), enabled by track_episodes
        self.spans: Optional[List[list]] = (
            [[] for _ in range(n_lanes)] if track_episodes else None
        )

    # -- episode intake ------------------------------------------------------

    def add_blob(self, blob: bytes) -> None:
        """Stage one episode from its wire-codec bytes — the exact frames
        ``EpisodeStore`` mirrors to shm batcher children."""
        from . import codec

        self.add_episode(codec.loads(blob))

    def add_episode(self, episode: Dict[str, Any]) -> None:
        """Decode one columnar episode into per-step record arrays and
        queue it on the shortest lane."""
        from .batch import _concat_columns
        from .replay import decompress_block

        cols = _concat_columns(
            [decompress_block(b) for b in episode["blocks"]]
        )
        T = int(episode["steps"])
        P = cols["prob"].shape[1]
        outcome = np.asarray(
            [episode["outcome"][p] for p in episode["players"]], np.float32
        )
        done = np.zeros((T,), bool)
        done[-1] = True
        rec = {
            "active": cols["tmask"].astype(np.float32),
            "observing": cols["omask"].astype(np.float32),
            "legal": cols["amask"] == 0.0,
            "action": cols["action"].astype(np.int32),
            "prob": cols["prob"].astype(np.float32),
            "value": cols["value"].astype(np.float32),
            "reward": cols["reward"].astype(np.float32),
            "ret": cols["ret"].astype(np.float32),
            "outcome": np.broadcast_to(outcome, (T, P)).copy(),
            "done": done,
        }
        obs_leaves, treedef = jax.tree.flatten(cols["obs"])
        for i, leaf in enumerate(obs_leaves):
            rec[f"obs{i}"] = np.asarray(leaf)
        if self.replay is None:
            spec = None
            if episode.get("obs_scale") is not None:
                # the quantization spec travels WITH the episode
                # (generation.py _finalize) — no env re-derivation here
                spec = list(zip(
                    np.asarray(episode["obs_scale"], np.float32).tolist(),
                    np.asarray(episode["obs_zero"], np.float32).tolist(),
                ))
            self._view = EpisodeObsView(P, treedef, len(obs_leaves), obs_spec=spec)
            self.replay = DeviceReplay(
                self._view, self.module, self.args, self.mesh,
                self.n_lanes, slots=self.slots,
            )
        lane = min(range(self.n_lanes), key=lambda i: self._qlen[i])
        if self.spans is not None:
            self.spans[lane].append(
                (self._qtotal[lane], self._qtotal[lane] + T - 1, episode)
            )
        self._queues[lane].append([rec, 0])
        self._qlen[lane] += T
        self._qtotal[lane] += T
        self.episodes_staged += 1
        self.steps_staged += T

    # -- chunk assembly + flush ----------------------------------------------

    def _take(self, lane: int, k: int) -> Dict[str, np.ndarray]:
        """Pop ``k`` steps off a lane's queue (possibly spanning episode
        boundaries) as one concatenated record dict with (k, ...) leaves."""
        q = self._queues[lane]
        parts: List[Dict[str, np.ndarray]] = []
        left = k
        while left > 0:
            rec, off = q[0]
            T = rec["done"].shape[0]
            take = min(left, T - off)
            parts.append({key: val[off:off + take] for key, val in rec.items()})
            if off + take == T:
                q.pop(0)
            else:
                q[0][1] = off + take
            left -= take
        self._qlen[lane] -= k
        if len(parts) == 1:
            return parts[0]
        return {
            key: np.concatenate([p[key] for p in parts]) for key in parts[0]
        }

    def ready(self) -> bool:
        """True when every lane has a full chunk queued."""
        return self.replay is not None and min(self._qlen) >= self.chunk_steps

    def flush(self) -> int:
        """Fold every complete (chunk, lanes) block into the rings; returns
        the number of chunks ingested.  Stats fetches are deferred
        (ingest_counted defer=True) so consecutive chunks overlap."""
        n = 0
        K = self.chunk_steps
        while self.ready():
            chunks = [self._take(lane, K) for lane in range(self.n_lanes)]
            records = {
                key: np.stack([c[key] for c in chunks], axis=1)  # (K, B, ...)
                for key in chunks[0]
            }
            self.replay.ingest_counted(records, defer=True)
            self.chunks_flushed += 1
            n += 1
        return n

    def eligible(self) -> int:
        """Sampleable window starts currently resident (host sync)."""
        if self.replay is None:
            return 0
        return self.replay.eligible_count()

    def drain(self) -> None:
        """Settle deferred stats and block on the last in-flight ingest."""
        if self.replay is not None:
            self.replay.flush_counted()
            self.replay.drain()
