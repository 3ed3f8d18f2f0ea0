"""Host-bypass batch assembly: ``batch_pipeline: device``.

Host batch assembly (make_batch + the ~43 MB/update observation H2D
re-upload on HungryGeese) feeds the device a small part of what a staged
step consumes, and no batcher count fixes a per-update host round-trip
(how small is not measured on today's code: PERF.md section 7,
`geese_hostfed`).  The Sebulba/Podracer lesson the repo already
builds on (PR 3) applies to the DATA plane too: when the host loses, take
the host out of the data path.

This pipeline is the drop-in (start()/batch()/stop()/stats()) that does
that for HOST-BORN episodes (worker actors, remote workers — the episodes
``device_replay: true`` cannot cover because its data never leaves the
device):

    EpisodeStore ── episodes (subscribe/snapshot, the same stream the
      │             shm plane mirrors to its children)
      ▼
    feeder thread: decode once -> DeviceEpisodeStage lane queues
      -> fixed-size (chunk, lanes) ring ingest      [one H2D per chunk]
    batch(): jitted window sample+assembly FROM the rings
      -> device-resident (B, T, P, ...) batch       [zero H2D]

make_batch, the C fill kernels, and the per-update observation upload all
leave the hot loop: each episode's bytes cross to the device exactly once,
and every training batch after that is gathers on device memory.  Window
assembly reuses DeviceReplay's sampling programs, so sampling parity with
make_batch is pinned by the same key-by-key tests as the streaming path
(tests/test_device_stage.py).

The shm plane stays the default and the fallback: this pipeline refuses
misconfigured stage modes at construction time, and ``make_pipeline``
then falls back loudly.

Multi-process (docs/performance.md §Pod-slice topology): each process
stages its OWN host-born episodes into rings on its LOCAL devices and
samples ``batch_size / num_processes`` rows per update; the local rows
hop through host once (one D2H of the sampled windows, not the per-step
observation re-upload this plane exists to kill) and re-enter the
collective mesh through ``TrainContext.put_batch`` — jax's
``make_array_from_process_local_data`` seam — so the cross-host train
step sees one global batch assembled from per-host rings.  The sampling
key is rank-decorrelated (fold_in(process_index)) or every process
would draw the same window indices from different rings.
"""

from __future__ import annotations

import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, Optional

from ..utils.trace import trace_event
from .device_replay import DeviceEpisodeStage, _lane_sharding
from .replay import EpisodeStore
from .trainer import PIPE_EVENT_KEYS, PIPE_STAT_KEYS


class DeviceBatchPipeline:
    """On-device batch assembly for host-born episodes.

    Drop-in for trainer.BatchPipeline: same constructor signature, same
    ``start()``/``batch()``/``stop()``/``stats()`` surface.  ``batch()``
    returns DEVICE-resident batches (dp-sharded exactly like
    ``TrainContext.put_batch`` output; a (k, B, ...) stack under
    ``fused_steps`` > 1), so the trainer's step dispatch consumes them
    with no host round-trip.
    """

    mode = "device"

    def __init__(self, args: Dict[str, Any], store: EpisodeStore, ctx,
                 stop_event: Optional[threading.Event] = None):
        import jax

        self.args = args
        self.store = store
        self.ctx = ctx
        self.stop_event = stop_event or threading.Event()
        from ..parallel import local_batch_size

        self._local_batch = local_batch_size(args["batch_size"])
        self._fused = max(1, args.get("fused_steps", 1))
        # multi-process: rings/stage/sampling live on this process's LOCAL
        # devices (each host assembles its own shard of the global batch);
        # the sampled rows cross to the collective ctx.mesh through
        # put_batch in batch() below.  Single-process: the stage shares
        # the train mesh and batch() returns device-resident output
        self._multiproc = jax.process_count() > 1
        if self._multiproc:
            from ..parallel.mesh import make_mesh

            self._mesh = make_mesh({"dp": -1}, jax.local_devices())
        else:
            self._mesh = ctx.mesh
        # raises on mode misconfiguration (recurrent net without turn
        # windows, missing observation flag, slots too shallow) — caught
        # by make_pipeline, which falls back loudly
        self.stage = DeviceEpisodeStage(
            ctx.module, args, self._mesh,
            n_lanes=int(args.get("device_stage_lanes", 8)),
            slots=int(args.get("device_stage_slots", 1024)),
            chunk_steps=int(args.get("device_stage_chunk", 64)),
        )
        self._key = jax.random.PRNGKey(int(args.get("seed", 0)) ^ 0xD17A)
        if self._multiproc:
            # rank-decorrelated draws: every process holds DIFFERENT
            # episodes, and must also draw different window indices (the
            # seed + 1009*rank pattern, as a key fold); single-process
            # keys are untouched so the existing parity pins hold
            self._key = jax.random.fold_in(self._key, jax.process_index())
        self._sampler = None
        self._eligible = False
        self._started = False
        self._lock = threading.Lock()
        self._stats: Dict[str, float] = {k: 0.0 for k in PIPE_STAT_KEYS}
        self._stats.update({k: 0.0 for k in PIPE_EVENT_KEYS})
        self._stats.update(batches=0.0, device_queue_depth_sum=0.0, gets=0.0)
        self._pending: deque = deque()
        self._pending_cv = threading.Condition()

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self._started:
            return
        self._started = True
        # subscribe BEFORE snapshotting (same reasoning as the shm plane:
        # an episode landing in between is staged twice, which only skews
        # lane balance slightly; missing one is a hole forever)
        self.store.subscribe(self._on_episodes)
        snapshot = self.store.snapshot()
        with self._pending_cv:
            self._pending.extend(snapshot)
            self._pending_cv.notify()
        self._feeder_thread = threading.Thread(
            target=self._feeder_loop, daemon=True
        )
        self._feeder_thread.start()

    def _on_episodes(self, episodes) -> None:
        with self._pending_cv:
            self._pending.extend(episodes)
            self._pending_cv.notify()

    def _feeder_loop(self) -> None:
        """Decode + stage + flush on a dedicated thread: the decode cost is
        paid once per EPISODE (not per update), and the ingest dispatches
        take the mesh's dispatch locks like every multi-device program."""
        try:
            while not self.stop_event.is_set():
                with self._pending_cv:
                    if not self._pending:
                        self._pending_cv.wait(timeout=0.3)
                    batch = list(self._pending)
                    self._pending.clear()
                if not batch:
                    continue
                t0 = time.perf_counter()
                for episode in batch:
                    try:
                        self.stage.add_episode(episode)
                    except Exception:
                        # one malformed episode must not take down the
                        # whole assembly plane (the shm feeder tolerates
                        # the same); the flush/ingest path below failing
                        # IS fatal — that's ring state, not one input
                        traceback.print_exc()
                t1 = time.perf_counter()
                self.stage.flush()
                t2 = time.perf_counter()
                with self._lock:
                    # assemble = host decode/staging, put = ring ingest
                    # (the once-per-chunk H2D) — same stat vocabulary as
                    # the host pipelines so the trainer's diffs apply
                    self._stats["assemble_s"] += t1 - t0
                    self._stats["put_s"] += t2 - t1
        except Exception:
            # a dead silent pipeline deadlocks the trainer — fail loudly
            traceback.print_exc()
            self.stop_event.set()
        finally:
            try:
                self.stage.drain()
            except Exception:
                pass

    # -- consumer side -------------------------------------------------------

    def _build_sampler(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ..parallel.mesh import dispatch_serialized

        replay = self.stage.replay
        mesh = self._mesh
        B, fused = self._local_batch, self._fused
        rep = NamedSharding(mesh, PartitionSpec())
        out_shard = (
            NamedSharding(mesh, PartitionSpec("dp"))
            if fused == 1
            else NamedSharding(mesh, PartitionSpec(None, "dp"))
        )

        def sample(rings, key):
            batch = replay._sample(rings, key, fused * B)
            if fused > 1:
                # rows are i.i.d. draws, so a reshape to the stacked
                # (k, B, ...) layout put_batches produces is equivalent
                # to k independent B-row samples
                batch = jax.tree.map(
                    lambda x: x.reshape((fused, B) + x.shape[1:]), batch
                )
            return batch

        ring_shard = _lane_sharding(mesh, replay.rings)
        fn = jax.jit(sample, in_shardings=(ring_shard, rep),
                     out_shardings=out_shard)

        def call(key):
            # replay.rings is read INSIDE the locked lambda: a concurrent
            # ingest donates the old ring buffers under the same locks
            return dispatch_serialized(lambda: fn(replay.rings, key), mesh)

        return call

    def batch(self):
        """Next device-resident batch, or None when shutting down.  The
        None on stop is LOAD-BEARING: the trainer's epoch loop has no
        other exit once update_flag stays false (same contract as the
        host pipelines' batch())."""
        import jax

        if self.stop_event.is_set():
            return None
        with self._lock:
            self._stats["gets"] += 1
        if not self._eligible:
            t0 = time.perf_counter()
            warned_at = t0
            while not self.stop_event.is_set():
                if self.stage.eligible() > 0:
                    self._eligible = True
                    break
                now = time.perf_counter()
                if now - warned_at > 30.0:
                    # a chunk flushes only when EVERY lane has chunk steps
                    # queued — a too-large lanes x chunk for the episode
                    # supply waits here forever; say so instead of hanging
                    # silently
                    warned_at = now
                    import sys

                    print(
                        f"[handyrl_tpu] device batch pipeline waiting for "
                        f"sampleable windows ({now - t0:.0f}s): "
                        f"{self.stage.steps_staged} steps staged over "
                        f"{self.stage.n_lanes} lanes, first flush needs "
                        f"{self.stage.n_lanes * self.stage.chunk_steps} — "
                        "lower device_stage_lanes/device_stage_chunk if "
                        "this persists",
                        file=sys.stderr,
                    )
                time.sleep(0.05)
            wait = time.perf_counter() - t0
            with self._lock:
                self._stats["ready_wait_s"] += wait
            trace_event("pipe.ready_wait", wait, plane="pipeline", mode="device")
            if not self._eligible:
                return None
        if self._sampler is None:
            self._sampler = self._build_sampler()
        self._key, sub = jax.random.split(self._key)
        t0 = time.perf_counter()
        out = self._sampler(sub)
        if self._multiproc:
            # the one deliberate host hop of the multi-process path: the
            # local rows leave the local mesh ONCE (B/nprocs sampled
            # windows, not the per-step observation re-upload this plane
            # kills) and re-enter the collective mesh via put_batch's
            # make_array_from_process_local_data seam, which takes host
            # buffers by contract
            # graftlint: allow[HS001] reason=documented local-shard crossing: make_array_from_process_local_data consumes host buffers; one D2H of sampled rows per update, not per step
            host = jax.device_get(out)
            if self._fused == 1:
                out = self.ctx.put_batch(host)
            else:
                out = self.ctx.put_batches(
                    [
                        jax.tree.map(lambda x, i=i: x[i], host)
                        for i in range(self._fused)
                    ]
                )
        with self._lock:
            self._stats["sample_s"] += time.perf_counter() - t0
            self._stats["batches"] += self._fused
        return out

    # -- teardown / introspection -------------------------------------------

    def stop(self) -> None:
        self.stop_event.set()
        try:
            self.store.unsubscribe(self._on_episodes)
        except Exception:
            pass
        # join the feeder before returning: tearing the interpreter down
        # while a daemon thread is inside an XLA execute aborts the
        # process (C++ terminate at exit) — same reasoning as the
        # learner's rollout-thread join
        feeder = getattr(self, "_feeder_thread", None)
        if feeder is not None and feeder is not threading.current_thread():
            feeder.join(timeout=30.0)
        try:
            self.stage.drain()
        except Exception:
            pass

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = dict(self._stats)
        out["mode"] = self.mode
        out["episodes_staged"] = self.stage.episodes_staged
        out["steps_staged"] = self.stage.steps_staged
        out["chunks_flushed"] = self.stage.chunks_flushed
        return out
