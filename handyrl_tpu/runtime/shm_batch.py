"""GIL-free batch-assembly plane: batcher PROCESSES + shared-memory ring.

The threaded BatchPipeline (runtime/trainer.py) keeps every make_batch on
the learner process's GIL, where it contends with the inference engine,
the worker threads and jax dispatch (no cell measures it yet: PERF.md
section 7, `geese_hostfed`).  This module moves
assembly off the GIL entirely, the IMPALA/HandyRL decoupled-batcher
design point (reference train.py:271-401 forks num_batchers processes):

    parent                                children (num_batchers processes)
    ------                                ---------------------------------
    EpisodeStore ──codec blobs──▶ feed_q ─▶ replica EpisodeStore
                                            sample local_batch windows
    free_q[i] ────────── slot indices ────▶ fill_batch into shm slot views
    ready pipe ◀─ fixed-size records ◀────┘
    device-put thread: slot views ─▶ ctx.put_batch ─▶ device queue

    Both slot channels are designed to survive a SIGKILL'd child, which
    dies holding whatever lock it was inside:

    * Free slots travel through PER-CHILD ``mp.Queue``s (the parent deals
      recycled slots round-robin), not one shared queue — ``Queue.get``
      holds its reader lock for the whole blocking wait, so a kill almost
      always catches the victim INSIDE the lock; per-child queues mean a
      dead child can only poison itself.
    * Ready messages travel over a raw ``os.pipe`` as fixed-size structs
      (far below PIPE_BUF, so every write is kernel-atomic and LOCK-FREE).
      An ``mp.Queue`` here would wedge the survivors a different way: each
      writer's queue-feeder thread takes a shared write lock per message,
      and a kill mid-write leaves that lock dead — the survivors' feeders
      then buffer forever and nothing reaches the parent (observed as
      qsize growing while poll() stays empty).  A killed pipe writer, by
      contrast, leaves a whole record or nothing.

Zero-copy by construction: batches have fixed (B, T, P, ...) shapes
(runtime/batch.py), so each ring slot is a preallocated columnar layout in
one ``multiprocessing.shared_memory`` segment.  Children write into numpy
views over their mapping; the parent wraps the SAME bytes as views and
hands them to ``TrainContext.put_batch`` — no pickling and no host-side
memcpy anywhere on the consumer path.  A slot is recycled only after
``jax.block_until_ready`` on the device transfer, so an in-flight H2D DMA
can never read a half-overwritten slot.

Episodes travel to the children once, as wire-codec bytes (never pickle,
matching the trust model of runtime/codec.py), and each child maintains
its own recency-biased replica store — per-batch sampling then costs the
parent nothing.  Every stage is timed (sample / assemble / free-slot wait
/ ready wait / device put / device-queue depth) and surfaced through
``stats()`` into metrics.jsonl.

Supervision (docs/fault_tolerance.md): the parent watches its children.
An OOM-killed / SIGKILL'd batcher process no longer starves the trainer
silently — the consumer loop notices the dead child, reclaims every ring
slot dealt to it (the parent stamps a shared ownership array BEFORE each
deal, so no slot is ever unattributed; a per-slot generation counter
makes any in-flight ready message for a reclaimed slot self-invalidating,
so a slot can never circulate twice), redistributes those slots to the
survivors, respawns the child up to ``batcher_max_restarts`` times, and
past that — or if the ring stays wedged for ``batcher_stall_timeout``
after a death (the narrow remaining window: a SIGKILL inside the shared
ready queue's write lock) — degrades loudly to the threaded pipeline.
Deaths, restarts and the fallback flip are counted in ``stats()`` and
land in metrics.jsonl as ``pipe_batcher_*`` events.
"""

from __future__ import annotations

import atexit
import multiprocessing as mp
import os
import queue as thqueue
import struct
import sys
import threading
import time
import traceback
from collections import deque
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils.trace import trace_event
from . import codec
from .batch import fill_batch, make_batch
from .connection import _wait_io
from .replay import EpisodeStore
from .trainer import PIPE_EVENT_KEYS, PIPE_STAT_KEYS

_ALIGN = 64  # cache-line-align every leaf inside a slot

# one ready message: slot (-1 = "this child hit an exception and is
# exiting"), slot generation, sample/assemble/free-wait timings.  36 bytes,
# far under PIPE_BUF (>= 512 by POSIX, 4096 on Linux): os.write of a whole
# record is atomic, so records from concurrent children never interleave
# and a SIGKILL'd writer can never leave a torn record in the pipe
_READY_REC = struct.Struct("=iQddd")


def slot_spec(template: Dict[str, Any]):
    """(nested spec, slot_bytes) for one batch.

    The spec mirrors the batch dict structure with ndarray leaves replaced
    by ``("leaf", shape, dtype_str, offset)``; containers are plain
    dict/list/tuple nodes, so the whole spec is picklable for spawn-start
    children and rebuilds identically on both sides of the fork (dict keys
    are laid out sorted, matching jax's pytree flattening order)."""
    offset = 0

    def walk(node):
        nonlocal offset
        if isinstance(node, np.ndarray):
            here = offset
            offset += (node.nbytes + _ALIGN - 1) // _ALIGN * _ALIGN
            return ("leaf", tuple(node.shape), node.dtype.str, here)
        if isinstance(node, dict):
            return ("dict", {k: walk(node[k]) for k in sorted(node)})
        if isinstance(node, (list, tuple)):
            return ("seq", isinstance(node, tuple), [walk(x) for x in node])
        raise TypeError(f"batch leaf {type(node).__name__} is not shm-mappable")

    spec = walk(template)
    return spec, max(offset, _ALIGN)


def slot_views(spec, buf, base: int):
    """Rebuild the batch dict as numpy views into ``buf`` at ``base``."""
    kind = spec[0]
    if kind == "leaf":
        _, shape, dtype_str, off = spec
        return np.ndarray(shape, dtype=np.dtype(dtype_str), buffer=buf, offset=base + off)
    if kind == "dict":
        return {k: slot_views(v, buf, base) for k, v in spec[1].items()}
    _, is_tuple, items = spec
    seq = [slot_views(s, buf, base) for s in items]
    return tuple(seq) if is_tuple else seq


def _drain_feed(feed_q, store: EpisodeStore) -> None:
    while True:
        try:
            blob = feed_q.get_nowait()
        except thqueue.Empty:
            return
        try:
            store.extend([codec.loads(blob)])
        except Exception:
            traceback.print_exc()


def _batcher_main(shm_name, spec, slot_bytes, args, local_batch, seed,
                  feed_q, free_q, ready_w, stop, slot_gen) -> None:
    """Child entry point: replica store -> sample -> fill shm slot.

    Runs under fork (Linux default) or spawn; everything it needs arrives
    through its arguments, and fork-inherited module state that could
    carry a held lock is re-created first.  Never touches jax arrays or
    the device — pure numpy + zlib + codec, i.e. C code that releases the
    GIL it no longer shares with the learner anyway.

    Crash-safety protocol: ``free_q`` is this child's PRIVATE free-slot
    queue — the parent stamped ``owner[slot]`` before dealing each index
    into it, so every slot this process holds (queued or in hand) is
    attributed and reclaimable if it dies, and a kill inside the queue's
    reader lock wedges nobody else.  The child snapshots
    ``slot_gen[slot]`` at claim time and sends it with the ready message;
    reclamation bumps the generation, invalidating any message still in
    flight so a reclaimed slot can never circulate twice."""
    import random
    import signal

    from . import replay

    # fork copies the learner's SIGTERM/SIGINT drain handlers into this
    # process, where they only flip flags on a dead copy of the learner —
    # a terminate() from the parent would be swallowed and the child
    # would survive its own teardown.  Restore the default disposition
    # so this process stays killable.
    for _sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(_sig, signal.SIG_DFL)
        except (ValueError, OSError):
            pass

    replay.reset_block_cache()
    random.seed((int(seed) & 0xFFFFFFFF) * 1_000_003 + os.getpid())
    views_by_slot: Dict[int, Dict[str, Any]] = {}
    shm = None
    try:
        # NOTE: attaching registers the segment with the resource tracker a
        # second time, but fork/spawn children share the parent's tracker
        # process, so the name is a set entry — the parent's close() path
        # unlinks and unregisters exactly once and nothing leaks
        shm = shared_memory.SharedMemory(name=shm_name)
        store = EpisodeStore(int(args["maximum_episodes"]))
        fs = args["forward_steps"]
        bs = args["burn_in_steps"]
        cs = args["compress_steps"]
        while not stop.value:
            _drain_feed(feed_q, store)
            t0 = time.perf_counter()
            windows: List[Dict[str, Any]] = []
            while len(windows) < local_batch:
                if stop.value:
                    return
                w = store.sample_window(fs, bs, cs)
                if w is None:
                    _drain_feed(feed_q, store)
                    time.sleep(0.05)
                    continue
                windows.append(w)
            t_sample = time.perf_counter() - t0

            t0 = time.perf_counter()
            slot = None
            while slot is None:
                try:
                    slot = free_q.get(timeout=0.2)
                except thqueue.Empty:
                    if stop.value:
                        return
                    _drain_feed(feed_q, store)
            gen = slot_gen[slot]
            t_free = time.perf_counter() - t0

            out = views_by_slot.get(slot)
            if out is None:
                out = views_by_slot[slot] = slot_views(spec, shm.buf, slot * slot_bytes)
            t0 = time.perf_counter()
            fill_batch(windows, args, out)
            os.write(ready_w, _READY_REC.pack(
                slot, gen, t_sample, time.perf_counter() - t0, t_free
            ))
    except Exception:
        traceback.print_exc()  # full detail to stderr; the record below
        # just tells the parent this child is exiting abnormally
        try:
            os.write(ready_w, _READY_REC.pack(-1, 0, 0.0, 0.0, 0.0))
        except Exception:
            pass
    finally:
        views_by_slot.clear()
        if shm is not None:
            try:
                import gc

                gc.collect()  # numpy views pin shm.buf; drop them first
                shm.close()
            except BufferError:
                pass  # process exit unmaps regardless


class ShmBatchPipeline:
    """Process batchers writing into a shared-memory slot ring.

    Drop-in for trainer.BatchPipeline: same constructor signature, same
    ``start()``/``batch()`` surface, plus ``stop()`` (join children +
    unlink the segment) and ``stats()`` (per-stage cumulative timings +
    supervision event counters).
    """

    mode = "shm"

    def __init__(self, args: Dict[str, Any], store: EpisodeStore, ctx,
                 stop_event: Optional[threading.Event] = None):
        self.args = args
        self.store = store
        self.ctx = ctx
        self.stop_event = stop_event or threading.Event()
        from ..parallel import local_batch_size

        self._local_batch = local_batch_size(args["batch_size"])
        self._fused = max(1, args.get("fused_steps", 1))
        # the consumer double-buffers H2D transfers (one group transferring
        # while the next is drained from the ring), so up to TWO fused
        # groups' slots can be pinned in flight at once; fewer than
        # 2*fused + 1 free-able slots would stall the children exactly when
        # the overlap is supposed to keep them filling.  The clamp lives in
        # config.effective_shm_slots — validate_args checks num_batchers
        # against the same number
        from ..config import effective_shm_slots

        self._n_slots = effective_shm_slots(dict(args, fused_steps=self._fused))
        self._device_queue: thqueue.Queue = thqueue.Queue(
            maxsize=args.get("prefetch_batches", 2)
        )
        # fork shares the already-warm parent image (children need numpy +
        # this package, not a fresh interpreter) and the ready pipe rides
        # fork fd inheritance.  The parent holds the accelerator and runs
        # its runtime's threads; the children never call into jax
        # (_spawn_child), so they never touch the device client they
        # inherited
        self._mp = mp.get_context("fork")
        self._procs: List[Any] = []
        self._feed_qs: List[Any] = []
        self._slot_views = None
        self._shm: Optional[shared_memory.SharedMemory] = None
        self._mp_stop = None
        self._started = False
        self._closed = False
        self._fallback = None
        self._lock = threading.Lock()
        self._stats: Dict[str, float] = {k: 0.0 for k in PIPE_STAT_KEYS}
        self._stats.update({k: 0.0 for k in PIPE_EVENT_KEYS})
        self._stats.update(batches=0.0, device_queue_depth_sum=0.0, gets=0.0)
        self._pending: deque = deque()
        self._pending_cv = threading.Condition()
        # supervision state (consumer-thread only, except the counters)
        self._max_restarts = int(args.get("batcher_max_restarts", 3))
        self._stall_timeout = float(args.get("batcher_stall_timeout", 60.0))
        self._restarts = 0
        self._had_death = False
        self._last_child_check = 0.0
        self._last_death = 0.0

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        try:
            self._start_impl()
        except BaseException:
            # no quiet hand-over to the threaded pipeline: a run asked for
            # the shm plane, so a plane that cannot come up is an error
            self.close()
            raise

    def _sample_template_windows(self):
        windows = []
        while len(windows) < self._local_batch:
            if self.stop_event.is_set():
                return None
            w = self.store.sample_window(
                self.args["forward_steps"],
                self.args["burn_in_steps"],
                self.args["compress_steps"],
            )
            if w is None:
                time.sleep(0.2)
                continue
            windows.append(w)
        return windows

    def _start_impl(self) -> None:
        windows = self._sample_template_windows()
        if windows is None:
            return  # shutting down before any episode arrived
        # one reference batch pins the slot layout (fixed shapes) AND
        # anchors the parity contract: children produce bit-identical
        # bytes for the same windows (tests/test_shm_pipeline.py)
        template = make_batch(windows, self.args)
        self._spec, self._slot_bytes = slot_spec(template)
        self._shm = shared_memory.SharedMemory(
            create=True, size=self._slot_bytes * self._n_slots
        )
        atexit.register(self._unlink_quiet)
        self._ready_r, self._ready_w = os.pipe()
        self._ready_buf = b""
        # lock-FREE stop flag, not mp.Event: Event.is_set() takes the
        # event's shared condition lock, and children poll the flag in
        # their hottest loop — a SIGKILL landing inside that lock would
        # wedge every surviving child forever.  A raw shared int has no
        # lock to die holding.
        self._mp_stop = self._mp.Value("i", 0, lock=False)
        # slot ownership + generation (see _batcher_main docstring for the
        # crash-safety protocol); both are lock-free because the PARENT is
        # the only writer: owner[slot] is stamped before each deal and
        # cleared on receipt, slot_gen[slot] bumps only while the slot is
        # in the parent's domain
        self._owner = self._mp.Array("i", self._n_slots, lock=False)
        self._slot_gen = self._mp.Array("L", self._n_slots, lock=False)
        for i in range(self._n_slots):
            self._owner[i] = -1
        self._deal_rr = 0
        self._orphan_slots: List[int] = []
        self._slot_views = [
            slot_views(self._spec, self._shm.buf, i * self._slot_bytes)
            for i in range(self._n_slots)
        ]
        self._spawn_children()

    def _spawn_children(self) -> None:
        # subscribe BEFORE snapshotting: an episode landing in between is
        # delivered twice (snapshot + listener) rather than lost — a
        # duplicate in a replica store only nudges sampling weights, a
        # missing one is a hole in the children's data forever
        self.store.subscribe(self._on_episodes)
        snapshot = [codec.dumps(ep) for ep in self.store.snapshot()]
        n = max(1, int(self.args["num_batchers"]))
        self._procs = [None] * n
        self._feed_qs = [None] * n
        self._free_qs = [None] * n
        for i in range(n):
            self._spawn_child(i, snapshot)
        for slot in range(self._n_slots):
            self._deal_slot(slot)
        threading.Thread(target=self._feeder_loop, daemon=True).start()
        self._consumer_thread = threading.Thread(
            target=self._device_put_loop, daemon=True
        )
        self._consumer_thread.start()

    def _spawn_child(self, i: int, snapshot: Optional[List[bytes]] = None) -> None:
        """(Re)start batcher child ``i`` with a fresh replica feed from the
        parent's authoritative store."""
        feed_q = self._mp.Queue()
        # publish BEFORE snapshotting — the respawn path runs with the
        # feeder live, and an episode arriving between the snapshot and
        # the publication would go to the dead child's orphaned queue: a
        # permanent hole in the replica.  This order can deliver such an
        # episode twice (live feed + snapshot), which replica stores
        # tolerate by design (same reasoning as subscribe-before-snapshot
        # in _spawn_children)
        self._feed_qs[i] = feed_q
        if snapshot is None:
            snapshot = [codec.dumps(ep) for ep in self.store.snapshot()]
        for blob in snapshot:
            feed_q.put(blob)
        free_q = self._mp.Queue()
        self._free_qs[i] = free_q
        proc = self._mp.Process(
            target=_batcher_main,
            args=(self._shm.name, self._spec, self._slot_bytes, self.args,
                  self._local_batch,
                  int(self.args.get("seed", 0)) + i + 7919 * self._restarts,
                  feed_q, free_q, self._ready_w, self._mp_stop,
                  self._slot_gen),
            daemon=True,
        )
        import warnings

        with warnings.catch_warnings():
            # jax warns that fork + its internal threads can deadlock;
            # these children never call into jax/XLA (pure numpy +
            # zlib + codec, and replay.reset_block_cache() re-creates
            # the one inherited lock they touch), so the general
            # warning does not apply to this fork
            warnings.filterwarnings(
                "ignore", message="os.fork", category=RuntimeWarning
            )
            proc.start()
        self._procs[i] = proc

    def _on_episodes(self, episodes: List[Dict[str, Any]]) -> None:
        # store.extend runs on the learner's server thread — only queue a
        # reference here; the feeder thread pays for encoding
        with self._pending_cv:
            self._pending.extend(episodes)
            self._pending_cv.notify()

    def _feeder_loop(self) -> None:
        try:
            while not self.stop_event.is_set():
                with self._pending_cv:
                    if not self._pending:
                        self._pending_cv.wait(timeout=0.3)
                    batch = list(self._pending)
                    self._pending.clear()
                for episode in batch:
                    blob = codec.dumps(episode)
                    for feed_q in tuple(self._feed_qs):
                        if feed_q is None:
                            continue
                        try:
                            feed_q.put(blob)
                        except Exception:
                            pass  # queue of a child being replaced; its
                            # successor reseeds from the store snapshot
        except Exception:
            traceback.print_exc()

    # -- slot dealing --------------------------------------------------------

    def _deal_slot(self, slot: int) -> None:
        """Hand a free slot to a live child's private queue (round-robin),
        stamping ownership FIRST so the slot is attributed at every
        instant it is outside the parent's hands — a child killed at any
        point can have all its slots reclaimed."""
        if self._closed or self.stop_event.is_set():
            # teardown: close() may already have closed the free queues
            # under the consumer thread retiring its in-flight slots —
            # nothing will consume the slot again, parking it is enough
            self._orphan_slots.append(slot)
            return
        n = len(self._procs)
        for off in range(n):
            i = (self._deal_rr + off) % n
            if self._procs[i] is not None:
                self._deal_rr = (i + 1) % n
                self._owner[slot] = i
                try:
                    self._free_qs[i].put(slot)
                except (ValueError, OSError):  # closed under our feet
                    self._orphan_slots.append(slot)
                return
        # every child is currently dead (between death and respawn, or
        # headed for degradation): park the slot; respawn re-deals it
        self._orphan_slots.append(slot)

    # -- supervision ---------------------------------------------------------

    def _check_children(self) -> None:
        """Reap dead batcher children: reclaim their ring slots, respawn
        within budget, degrade to the thread pipeline past it.  Runs on
        the consumer thread only (throttled)."""
        # never respawn during teardown: children exiting 0 after
        # close() set mp_stop are a NORMAL stop, and a child forked here
        # races close()'s procs snapshot — it would be neither joined nor
        # terminated, and the interpreter's multiprocessing atexit join
        # then hangs the learner's exit on it
        if self.stop_event.is_set() or self._closed:
            return
        now = time.monotonic()
        if now - self._last_child_check < 0.25 or self._fallback is not None:
            return
        self._last_child_check = now
        for i, proc in enumerate(self._procs):
            if proc is None or proc.is_alive():
                continue
            exitcode = proc.exitcode
            self._procs[i] = None
            self._had_death = True
            self._last_death = now
            with self._lock:
                self._stats["batcher_deaths"] += 1
            # reclaim every slot dealt to the dead child — queued in its
            # private free queue or claimed in its hands, all are stamped
            # with its index.  Bump the generation FIRST: any ready
            # message the dead child managed to send is now stale and will
            # be discarded, so a slot can never circulate twice.  The dead
            # child's queue is abandoned unread (its reader lock may have
            # died with it); the slots are re-dealt to the survivors.
            reclaimed = []
            for slot in range(self._n_slots):
                if self._owner[slot] == i:
                    self._owner[slot] = -1
                    self._slot_gen[slot] += 1
                    reclaimed.append(slot)
            # retire BOTH of the dead child's queues.  cancel_join_thread
            # is the critical call: the feed queue's internal feeder
            # thread can be blocked forever on a full unread pipe, and
            # multiprocessing's exit finalizer would otherwise join it —
            # hanging learner shutdown after any batcher death
            for old_q in (self._free_qs[i], self._feed_qs[i]):
                if old_q is not None:
                    try:
                        old_q.cancel_join_thread()
                        old_q.close()
                    except Exception:
                        pass
            self._free_qs[i] = None
            self._feed_qs[i] = None
            print(
                f"[handyrl_tpu] batcher process {i} died (exitcode {exitcode}); "
                f"reclaimed ring slots {reclaimed}",
                file=sys.stderr,
            )
            for slot in reclaimed:
                self._deal_slot(slot)  # survivors keep the ring flowing NOW
            if self._restarts >= self._max_restarts:
                self._degrade(
                    f"restart budget exhausted ({self._max_restarts})"
                )
                return
            self._restarts += 1
            with self._lock:
                self._stats["batcher_restarts"] += 1
            try:
                self._spawn_child(i)
                print(
                    f"[handyrl_tpu] batcher process {i} respawned "
                    f"(restart {self._restarts}/{self._max_restarts})",
                    file=sys.stderr,
                )
            except Exception:
                traceback.print_exc()
                self._degrade("batcher respawn failed")
                return
            for slot in self._orphan_slots:
                self._deal_slot(slot)
            self._orphan_slots = []

    def _degrade(self, reason: str) -> None:
        """Swap in the threaded pipeline.  Loud: a degraded assembly plane
        changes the learner's throughput profile and must be visible in
        logs AND metrics (``pipe_batcher_fallback`` flips to 1, the
        ``pipeline`` mode field flips to 'thread')."""
        print(
            f"[handyrl_tpu] shm batch pipeline degrading to threaded "
            f"batchers: {reason}",
            file=sys.stderr,
        )
        from .trainer import BatchPipeline

        fallback = BatchPipeline(self.args, self.store, self.ctx, self.stop_event)
        with self._lock:
            # carry ALL cumulative counters across the mode flip — the
            # trainer diffs stage timings per epoch, so a fresh-zeroed
            # fallback would make the degradation epoch's pipe_* records
            # go negative; the event counts must survive too
            fallback._stats.update(self._stats)
            fallback._stats["batcher_fallback"] = 1.0
        fallback.start()
        self._fallback = fallback

    # -- consumer side -------------------------------------------------------

    def _ready_next_record(self):
        """Next whole record from the ready pipe, or None after ~0.3s of
        nothing.  Writes are atomic (<= PIPE_BUF) so only READS can split
        a record — the carry buffer handles that."""
        if len(self._ready_buf) < _READY_REC.size:
            try:
                _wait_io(self._ready_r, False, time.monotonic() + 0.3)
            except TimeoutError:  # covers socket.timeout (py>=3.10 alias)
                return None
            chunk = os.read(self._ready_r, 4096)
            if not chunk:
                return None  # all writers closed (teardown)
            self._ready_buf += chunk
        if len(self._ready_buf) < _READY_REC.size:
            return None
        record = _READY_REC.unpack(self._ready_buf[: _READY_REC.size])
        self._ready_buf = self._ready_buf[_READY_REC.size:]
        return record

    def _ready_get(self):
        t0 = time.perf_counter()
        t_enter = time.monotonic()
        while not self.stop_event.is_set():
            self._check_children()
            if self._fallback is not None:
                return None
            item = self._ready_next_record()
            if item is None:
                # no shared-lock wedge mode is known to remain, but keep a
                # last-resort watchdog: after a death, zero ready traffic
                # for this long means give up on the shm plane.  The clock
                # baselines on THIS call's entry (and the death, if later):
                # time the consumer spent elsewhere — device-queue
                # backpressure, a minutes-long first jit compile — must not
                # count as ring stall, or a death coinciding with an epoch
                # boundary would spuriously and permanently degrade
                if (
                    self._had_death
                    and time.monotonic() - max(t_enter, self._last_death)
                    > self._stall_timeout
                ):
                    self._degrade(
                        f"ring stalled > {self._stall_timeout:.0f}s after a "
                        "batcher death"
                    )
                    return None
                continue
            slot, gen, t_sample, t_assemble, t_free = item
            if slot < 0:
                # the child printed its traceback and is exiting;
                # supervision reaps it (respawn or degrade) — a one-off
                # fill failure must not take down the whole training run
                print(
                    "[handyrl_tpu] a batcher process failed (traceback on "
                    "its stderr) and will be reaped",
                    file=sys.stderr,
                )
                continue
            if gen != self._slot_gen[slot]:
                continue  # stale: produced by a child that died; the slot
                # was already reclaimed and may be refilling right now
            self._owner[slot] = -1
            self._had_death = False  # ring proved itself post-death: disarm
            wait = time.perf_counter() - t0
            with self._lock:
                self._stats["ready_wait_s"] += wait
            trace_event("pipe.ready_wait", wait, plane="pipeline", mode="shm")
            return slot, t_sample, t_assemble, t_free
        return None

    def _device_put_loop(self) -> None:
        import jax

        # Transfers IN FLIGHT: a group's slots recycle only after ITS
        # transfer completes (an in-flight DMA must never see a
        # half-overwritten slot), but the consumer no longer parks the
        # whole ring on that completion.  The old synchronous
        # block_until_ready here was what serialized the multi-batcher
        # plane: every child funnelled through one consumer that spent the
        # H2D time neither draining ready records nor recycling slots, so
        # past one child the extra fills just queued behind it.  Depth 2
        # (one group transferring while the next is drained + dispatched)
        # is the classic double buffer; _n_slots is clamped to 2*fused + 2
        # so the ring always has a dealable slot with two groups pinned.
        inflight: deque = deque()

        def retire_oldest() -> None:
            device_batch, done_slots = inflight.popleft()
            t0 = time.perf_counter()
            jax.block_until_ready(device_batch)
            with self._lock:
                self._stats["put_s"] += time.perf_counter() - t0
            for slot in done_slots:
                self._slot_gen[slot] += 1
                self._deal_slot(slot)

        try:
            while not self.stop_event.is_set():
                group, slots = [], []
                while len(group) < self._fused:
                    item = self._ready_get()
                    if item is None:
                        # shutdown OR degradation: recycle this partial
                        # group's slots so close() finds a consistent ring
                        for slot in slots:
                            self._slot_gen[slot] += 1
                            self._deal_slot(slot)
                        return
                    slot, t_sample, t_assemble, t_free = item
                    with self._lock:
                        self._stats["sample_s"] += t_sample
                        self._stats["assemble_s"] += t_assemble
                        self._stats["free_wait_s"] += t_free
                    group.append(self._slot_views[slot])
                    slots.append(slot)
                t0 = time.perf_counter()
                if self._fused > 1:
                    device_batch = self.ctx.put_batches(group)
                else:
                    device_batch = self.ctx.put_batch(group[0])
                with self._lock:
                    self._stats["put_s"] += time.perf_counter() - t0
                    self._stats["batches"] += len(group)
                # hand the (possibly still-transferring) batch to the
                # trainer FIRST — its async train-step dispatch overlaps
                # the rest of the H2D copy
                queued = self._put_device(device_batch)
                inflight.append((device_batch, slots))
                while len(inflight) > 1:
                    retire_oldest()
                if not queued:
                    return
        except Exception:
            traceback.print_exc()
            self.stop_event.set()
        finally:
            # settle every outstanding transfer (recycling its slots) so
            # close() — and a degradation's thread fallback — find a
            # consistent ring
            try:
                while inflight:
                    retire_oldest()
            except Exception:
                pass
            # degradation keeps the learner alive on the thread pipeline;
            # the shm plane itself still tears down completely
            self.close()

    def _put_device(self, item) -> bool:
        while not self.stop_event.is_set():
            try:
                self._device_queue.put(item, timeout=0.3)
                return True
            except thqueue.Full:
                # a full device queue parks the consumer thread HERE, not
                # in _ready_get — keep supervising or a child death would
                # go unnoticed until the trainer drains a batch
                self._check_children()
                if self._fallback is not None:
                    return False  # degraded: nobody drains this queue now
                continue
        return False

    def batch(self):
        """Next device batch, or None when shutting down."""
        if self._fallback is not None:
            return self._fallback.batch()
        with self._lock:
            self._stats["device_queue_depth_sum"] += self._device_queue.qsize()
            self._stats["gets"] += 1
        while not self.stop_event.is_set():
            if self._fallback is not None:
                # degraded mid-wait: the device queue will never fill again
                return self._fallback.batch()
            try:
                return self._device_queue.get(timeout=0.3)
            except thqueue.Empty:
                continue
        return None

    # -- teardown / introspection -------------------------------------------

    def stop(self) -> None:
        self.stop_event.set()
        self.close()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
        try:
            # a dead pipeline must stop mirroring the episode stream (its
            # feeder thread is gone; the pending deque would only grow) —
            # the fallback BatchPipeline samples the store directly
            self.store.unsubscribe(self._on_episodes)
        except Exception:
            pass
        if self._mp_stop is not None:
            self._mp_stop.value = 1
        procs = [p for p in self._procs if p is not None]
        for proc in procs:
            proc.join(timeout=5.0)
        for proc in procs:
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=2.0)
        for q in (
            [q for q in self._feed_qs if q is not None]
            + [q for q in getattr(self, "_free_qs", []) if q is not None]
        ):
            try:
                q.cancel_join_thread()
                q.close()
            except Exception:
                pass
        # the consumer thread polls/reads the ready fds: join it (unless
        # close() IS running on it, via _device_put_loop's finally) before
        # closing them — a reused fd number would otherwise let os.read
        # consume bytes from an unrelated descriptor
        consumer = getattr(self, "_consumer_thread", None)
        if consumer is not None and consumer is not threading.current_thread():
            consumer.join(timeout=5.0)
        for fd in (getattr(self, "_ready_r", None), getattr(self, "_ready_w", None)):
            if fd is not None:
                try:
                    os.close(fd)
                except OSError:
                    pass
        self._ready_r = self._ready_w = None
        self._slot_views = None
        if self._shm is not None:
            import gc

            gc.collect()  # release numpy views of shm.buf before unmapping
            try:
                self._shm.close()
            except BufferError:
                pass
            self._unlink_quiet()
        # the atexit safety net is only for pipelines that never reached
        # close(); keeping it would pin this instance (ctx/store/spec) for
        # process lifetime — a process may build several pipelines
        try:
            atexit.unregister(self._unlink_quiet)
        except Exception:
            pass

    def _unlink_quiet(self) -> None:
        shm = self._shm
        if shm is None:
            return
        try:
            shm.unlink()
        except FileNotFoundError:
            pass
        except Exception:
            pass

    def stats(self) -> Dict[str, Any]:
        if self._fallback is not None:
            return self._fallback.stats()
        with self._lock:
            out = dict(self._stats)
        out["mode"] = self.mode
        return out
