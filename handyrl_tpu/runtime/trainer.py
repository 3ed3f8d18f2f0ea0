"""Learner-side training loop: batch pipeline + epoch-cadenced SGD thread.

Process topology vs the reference (train.py:271-401): the reference forks
``num_batchers`` processes for make_batch and trains on the main GPU
thread.  The DEFAULT assembly plane here does the same, GIL-free —
batcher processes writing columnar batches straight into shared-memory
ring slots (runtime/shm_batch.py, ``batch_pipeline: shm``).  The threaded
pipeline below (``batch_pipeline: thread``) is kept as the portable
fallback and the in-process reference implementation:

    batcher threads (sample windows + columnar make_batch, numpy)
      -> host batch queue
      -> device-put thread (sharded transfer, double-buffered)
      -> device batch queue
      -> Trainer.train() loop calling the compiled train step

Both pipelines expose per-stage cumulative timings through ``stats()``
(sample / assemble / free-slot or host-queue wait / ready wait / device
put / device-queue depth); the trainer diffs them per epoch into
``pipe_*`` keys in metrics.jsonl so a nonzero ``input_wait_frac`` can be
attributed to a specific stage.

Epoch handoff keeps the reference semantics (train.py:343-346, 390-401):
``update()`` flips a flag and blocks on a 1-slot queue for the snapshot;
the learning rate follows the data-count EMA schedule (train.py:328-332,
383-385).
"""

from __future__ import annotations

import os
import queue
import signal
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..parallel import TrainContext
from ..utils.trace import trace_event, trace_span
from . import faults
from .batch import make_batch
from .replay import EpisodeStore


# the one canonical stage-key list: every consumer (both pipeline
# classes, the per-epoch metrics diff below)
# imports THIS tuple, so adding a stage cannot silently miss a site
PIPE_STAT_KEYS = ("sample_s", "assemble_s", "free_wait_s", "ready_wait_s", "put_s")

# supervision event counters (runtime/shm_batch.py): child deaths,
# respawns, and the degraded-to-thread flip.  Recorded CUMULATIVE in
# metrics.jsonl (pipe_batcher_*) — a nonzero value anywhere in the run
# means the assembly plane took a fault, and the per-epoch diff of rare
# events would mostly print zeros
PIPE_EVENT_KEYS = ("batcher_deaths", "batcher_restarts", "batcher_fallback")

# divergence-sentinel event counters, CUMULATIVE in metrics.jsonl for the
# same reason: in-step skips (nonfinite loss/grad-norm/lr), host-detected
# loss spikes (EMA detector), and verified-checkpoint rollbacks
SENTINEL_EVENT_KEYS = (
    "sentinel_skipped_steps",
    "sentinel_spike_steps",
    "sentinel_rollbacks",
    # rollbacks requested by the serving tier's quality sentinel
    # (flywheel/quality.py signal -> Learner -> request_rollback)
    "sentinel_flywheel_rollbacks",
)


def make_pipeline(args: Dict[str, Any], store: EpisodeStore, ctx: TrainContext,
                  stop_event: Optional[threading.Event] = None):
    """Build the configured batch-assembly pipeline.

    ``batch_pipeline: shm`` (the default) with ``num_batchers > 0`` forks
    GIL-free batcher processes writing into shared memory
    (runtime/shm_batch.py); ``device`` uploads host-born episodes ONCE
    into device ring buffers and samples/assembles training windows on
    device (runtime/device_batch.py — make_batch and the per-update
    observation H2D re-upload leave the hot loop); ``thread`` — or
    num_batchers 0 — uses the in-process threaded pipeline.  A configured
    pipeline that cannot be built raises: a run never trains through a
    different pipeline than the one it was asked for.  All three expose
    start()/batch()/stop()/stats()."""
    mode = args.get("batch_pipeline", "shm")
    if mode == "device":
        from .device_batch import DeviceBatchPipeline

        return DeviceBatchPipeline(args, store, ctx, stop_event)
    if mode == "shm" and int(args.get("num_batchers", 0)) > 0:
        from .shm_batch import ShmBatchPipeline

        return ShmBatchPipeline(args, store, ctx, stop_event)
    return BatchPipeline(args, store, ctx, stop_event)


class BatchPipeline:
    """Threaded replay -> numpy batch -> sharded device batch pipeline."""

    mode = "thread"

    def __init__(self, args: Dict[str, Any], store: EpisodeStore, ctx: TrainContext, stop_event: Optional[threading.Event] = None):
        self.args = args
        self.store = store
        self.ctx = ctx
        self.stop_event = stop_event or threading.Event()
        self._host_queue: queue.Queue = queue.Queue(maxsize=max(2, args["num_batchers"]))
        self._device_queue: queue.Queue = queue.Queue(maxsize=args.get("prefetch_batches", 2))
        self._threads: List[threading.Thread] = []
        self._stats_lock = threading.Lock()
        self._stats: Dict[str, float] = {k: 0.0 for k in PIPE_STAT_KEYS}
        self._stats.update({k: 0.0 for k in PIPE_EVENT_KEYS})
        self._stats.update(batches=0.0, device_queue_depth_sum=0.0, gets=0.0)
        # under jax.distributed each process assembles its local shard of
        # the global batch (TrainContext.put_batch builds the global array)
        from ..parallel import local_batch_size

        self._local_batch = local_batch_size(args["batch_size"])

    def start(self):
        if self._threads:
            return
        loops = [self._assemble_loop] * max(1, self.args["num_batchers"])
        self._threads = [
            threading.Thread(target=loop, daemon=True)
            for loop in loops + [self._device_put_loop]
        ]
        for thread in self._threads:
            thread.start()

    def _sample_windows(self):
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
                time.sleep(0.5)
                continue
            windows.append(w)
        return windows

    def _put(self, q: queue.Queue, item) -> bool:
        while not self.stop_event.is_set():
            try:
                q.put(item, timeout=0.3)
                return True
            except queue.Full:
                continue
        return False

    def _get(self, q: queue.Queue):
        while not self.stop_event.is_set():
            try:
                return q.get(timeout=0.3)
            except queue.Empty:
                continue
        return None

    def _bump(self, key: str, value: float) -> None:
        with self._stats_lock:
            self._stats[key] += value

    def _assemble_loop(self):
        try:
            while not self.stop_event.is_set():
                t0 = time.perf_counter()
                windows = self._sample_windows()
                if windows is None:
                    return
                t1 = time.perf_counter()
                batch = make_batch(windows, self.args)
                t2 = time.perf_counter()
                self._put(self._host_queue, batch)
                t3 = time.perf_counter()
                with self._stats_lock:
                    self._stats["sample_s"] += t1 - t0
                    self._stats["assemble_s"] += t2 - t1
                    # host-queue full = consumer-bound, the thread analogue
                    # of waiting for a free shm slot
                    self._stats["free_wait_s"] += t3 - t2
        except Exception:
            # a dead silent pipeline deadlocks the trainer — fail loudly
            traceback.print_exc()
            self.stop_event.set()

    def _host_get_timed(self):
        t0 = time.perf_counter()
        batch = self._get(self._host_queue)
        wait = time.perf_counter() - t0
        self._bump("ready_wait_s", wait)
        trace_event("pipe.ready_wait", wait, plane="pipeline", mode=self.mode)
        return batch

    def _device_put_loop(self):
        try:
            fused = self.args.get("fused_steps", 1)
            while not self.stop_event.is_set():
                if fused > 1:
                    group = []
                    while len(group) < fused:
                        batch = self._host_get_timed()
                        if batch is None:  # stop_event or shutdown sentinel
                            return
                        group.append(batch)
                    t0 = time.perf_counter()
                    device_batch = self.ctx.put_batches(group)
                else:
                    batch = self._host_get_timed()
                    if batch is None:
                        return
                    group = [batch]
                    t0 = time.perf_counter()
                    device_batch = self.ctx.put_batch(batch)
                with self._stats_lock:
                    self._stats["put_s"] += time.perf_counter() - t0
                    self._stats["batches"] += len(group)
                self._put(self._device_queue, device_batch)
        except Exception:
            traceback.print_exc()
            self.stop_event.set()

    def batch(self):
        """Next device batch, or None when shutting down."""
        with self._stats_lock:
            self._stats["device_queue_depth_sum"] += self._device_queue.qsize()
            self._stats["gets"] += 1
        return self._get(self._device_queue)

    def stop(self):
        """Every loop looks at the event within half a second; a put in
        flight ends first.  Waited for: an interpreter torn down while a
        daemon thread is inside a jax call aborts the process."""
        self.stop_event.set()
        for thread in self._threads:
            thread.join(timeout=10.0)

    def stats(self) -> Dict[str, float]:
        with self._stats_lock:
            out = dict(self._stats)
        out["mode"] = self.mode
        return out


class Trainer:
    """Runs the SGD loop in a daemon thread; epoch handoff via update()."""

    def __init__(self, args: Dict[str, Any], module, params, mesh):
        self.args = args
        self.ctx = TrainContext(module, args, mesh)
        self.state = self.ctx.init_state(params)
        # Host snapshot for checkpointing: the device state is donated into
        # every train step, so other threads must never read self.state.
        self.state_host = jax.device_get(self.state)
        self.store = EpisodeStore(args["maximum_episodes"])
        self.stop_event = threading.Event()
        self._stop_requested = False    # stop() was called by the owner

        self.fused = max(1, args.get("fused_steps", 1))
        if self.fused > 1 and jax.default_backend() == "cpu" and mesh.size > 1:
            # fused updates are a lax.scan whose body XLA:CPU executes
            # without its fast kernel runtime, with per-step collectives
            # across VIRTUAL devices sharing one thunk pool — measured as
            # minutes per dispatch (trainer stack-dumped inside
            # block_until_ready for 15+ min on the 8-device CPU mesh).
            # The knob is for real accelerators; degrade loudly.
            import sys

            print(
                "[handyrl_tpu] fused_steps > 1 on a multi-device CPU mesh "
                "executes scan-bodied collectives at pathological speed; "
                "forcing fused_steps=1 (use a TPU or a {'dp': 1} mesh)",
                file=sys.stderr,
            )
            self.fused = 1
        # the pipeline groups k host batches per device call iff the
        # trainer will actually run the fused path — same clamped value
        self.batcher = make_pipeline(
            dict(args, fused_steps=self.fused), self.store, self.ctx, self.stop_event
        )
        self._pipe_stats0: Dict[str, float] = {}
        # the run's FIRST batch wait is pipeline warm-up (template
        # assembly, child spawn + replica seeding, ring prefill), not
        # steady-state starvation — reported separately so the north-star
        # input_wait_frac stays honest (mirrors the plane watchdog's
        # compile-grace: warm-up must not read as a fault)
        self._warmup_wait_pending = True

        # device-resident replay (runtime/device_replay.py): set by the
        # Learner before run() when train_args.device_replay is true; the
        # SGD loop then samples on device instead of pulling host batches
        self.device_replay = None
        self._replay_key = jax.random.PRNGKey(args["seed"] ^ 0x7EA1)

        # split-plane param flow (runtime/plane.py): set by the Learner
        # under plane: split — the SGD loop then pushes a versioned param
        # copy to the actor mesh every param_refresh_updates steps
        self.param_cache = None
        self.param_refresh = max(1, int(args.get("param_refresh_updates", 8)))

        # -- multi-process epoch cadence (parallel/distributed.py) --------
        # Set by the Learner when jax.process_count() > 1: every train
        # step is then a cross-process collective, so epoch end / shutdown
        # / drain are agreed through the coordinator's broadcasts instead
        # of local flags — a local decision would wedge the other
        # processes inside a collective forever.  The collective watchdog
        # (parallel/health.py) bounds exactly that wedge when a peer dies.
        self.cadence = None
        self.collective_watchdog = None
        self.on_agreed_finish = None  # learner disarms the health plane here
        self.finished = False        # run() returned via an agreed stop
        self.drain_agreed = False    # the epoch ended with the DRAIN bit
        self._drain_flag = False     # coordinator: broadcast DRAIN next
        self._fault_wedge_process = False  # freeze before the next collective
        self._proceed_queue: queue.Queue = queue.Queue(maxsize=1)
        self._awaiting_proceed = False
        self._collective_dispatched = False  # arms the watchdog post-compile

        # -- divergence sentinel (docs/fault_tolerance.md) ----------------
        # The compiled step already SKIPPED any step with a nonfinite
        # loss/grad-norm/lr (parallel/train_step.py) — params can never be
        # poisoned by a single bad batch.  Host-side, this layer counts the
        # flags riding back in the epoch's metrics, runs a loss-spike EMA
        # detector over the same fetched values (PaLM-style: spikes are
        # expected events, Chowdhery et al. 2022), and escalates a streak of
        # ``sentinel_rollback_after`` consecutive bad steps to a rollback
        # onto the newest VERIFIED manifest checkpoint with re-seeded RNG.
        self.sentinel = bool(args.get("sentinel", True))
        self.sentinel_rollback_after = int(args.get("sentinel_rollback_after", 8))
        self._spike_factor = float(args.get("sentinel_spike_factor", 10.0))
        self._loss_ema_decay = float(args.get("sentinel_loss_ema_decay", 0.9))
        self._loss_ema: Optional[float] = None
        self._sentinel_streak = 0
        self.sentinel_events: Dict[str, int] = {k: 0 for k in SENTINEL_EVENT_KEYS}
        # quality-plane rollback request (epoch, or None): SET from the
        # learner's server thread (request_rollback), CONSUMED at the next
        # train_epoch entry on the trainer's own thread — the state reset
        # must never race an in-flight device step
        self._requested_rollback: Optional[int] = None
        # env-driven injections (runtime/faults.py): NaN lr window and
        # self-SIGTERM, parsed here so tests set the env before construction
        self._fault_nan = faults.nan_window()
        self._fault_sigterm = faults.sigterm_at_step()
        self._fault_sigterm_fired = False

        self.default_lr = 3e-8 * args["lr_scale"]
        self.data_cnt_ema = args["batch_size"] * args["forward_steps"]
        # FLOPs of one SGD update, resolved once at the end of the first
        # trained epoch (0.0 = tried, unavailable) — feeds the per-epoch
        # "mfu" stat in metrics.jsonl when the chip's peak rate is known
        self._flops_per_update: Optional[float] = None
        self.steps = 0
        self.last_loss: Dict[str, float] = {}
        self.stats: Dict[str, float] = {}  # step timing / input-starvation
        self.update_flag = False
        self.update_queue: queue.Queue = queue.Queue(maxsize=1)

    def save_payload(self, epoch: int) -> Dict[str, Any]:
        """Checkpoint payload: train state + epoch tag + lr-schedule EMA."""
        return {
            **self.state_host,
            "epoch": np.int32(epoch),
            "data_cnt_ema": np.float64(self.data_cnt_ema),
        }

    def drain_payload(self, epoch: int):
        """(params, state_payload, steps) for the preemption-drain
        checkpoint, all read from ONE ``state_host`` reference: the trainer
        thread swaps that reference atomically at epoch end, so even if the
        drain races a swap the three pieces stay mutually consistent
        (save_payload + params_host read it twice and could straddle)."""
        host = self.state_host
        payload = {
            **host,
            "epoch": np.int32(epoch),
            "data_cnt_ema": np.float64(self.data_cnt_ema),
        }
        return host["params"], payload, int(host["steps"])

    def load_state(self, path: str, expected_epoch: int) -> bool:
        """Resume params + Adam moments + step count + lr EMA from state.ckpt.

        The reference restarts Adam from scratch on resume (SURVEY.md §5.4);
        here the full state round-trips, so the lr schedule and moments
        continue where they left off.  Returns False (fresh optimizer) when
        the file was written at a different epoch than ``expected_epoch`` —
        restarting from an *earlier* snapshot is a branch, not a resume,
        and must not adopt the later run's weights.  An unreadable file
        (truncated by a crash mid-write in a pre-manifest layout, or
        garbage) also returns False — a broken optimizer checkpoint must
        degrade to a fresh optimizer, never kill the resume.
        """
        from .checkpoint import load_train_state

        try:
            host = load_train_state(path, self.save_payload(0))
        except Exception as exc:
            print(
                f"state.ckpt unreadable ({type(exc).__name__}: {exc}); "
                "resuming with a fresh optimizer"
            )
            return False
        ckpt_epoch = int(host.pop("epoch"))
        if ckpt_epoch != expected_epoch:
            print(
                f"state.ckpt is from epoch {ckpt_epoch}, not {expected_epoch}; "
                "branching with a fresh optimizer"
            )
            return False
        self.data_cnt_ema = float(host.pop("data_cnt_ema"))
        self.state = self.ctx.put_state(host)
        self.state_host = host
        self.steps = int(host["steps"])
        print(f"resumed train state at step {self.steps} from {path}")
        return True

    @property
    def lr(self) -> float:
        return self.default_lr * self.data_cnt_ema / (1 + self.steps * 1e-5)

    def params_host(self):
        return self.state_host["params"]

    def update(self):
        """Request an epoch boundary; blocks until the snapshot is ready.

        Before the warmup threshold no training has happened — return
        immediately so the learner keeps serving (reference train.py:343-346).

        On a multi-process FOLLOWER the boundary is not requested here at
        all: the coordinator's broadcast ends the epoch on every process,
        the snapshot lands in the queue, and the follower's learner calls
        this only once it sees the queue populated — so the get below
        never blocks on an epoch that was not already agreed.
        """
        if self.cadence is not None and not self.cadence.is_coordinator:
            while not self.stop_event.is_set():
                try:
                    return self.update_queue.get(timeout=1.0)
                except queue.Empty:
                    continue
            return None, self.steps
        if not self._warmed_up():
            return None, self.steps
        self.update_flag = True
        while not self.stop_event.is_set():
            try:
                return self.update_queue.get(timeout=1.0)
            except queue.Empty:
                continue
        return None, self.steps

    def proceed(self, stop: bool) -> None:
        """Multi-process coordinator only: the learner's continue/shutdown
        decision for the epoch whose snapshot it just consumed.  run()
        holds the next cadence collective until this arrives, then
        broadcasts the decision so every trainer stops (or continues)
        together.  A no-op unless run() is actually waiting — pre-warmup
        boundaries deliver no snapshot and expect no proceed."""
        if self.cadence is None or not self._awaiting_proceed:
            return
        self._proceed_queue.put(bool(stop))

    def request_drain(self) -> None:
        """Preemption drain entry point, cadence-aware.  Single-process:
        stop the trainer mid-epoch (the historical behavior).  Multi-
        process coordinator: set the DRAIN bit instead — the next cadence
        broadcast ends the epoch on EVERY process coherently (a hard local
        stop would leave the peers wedged in the next collective).  A
        follower getting a local SIGTERM cannot drive the cadence; it
        waits for the agreed drain or its drain deadline."""
        if self.cadence is None:
            self.stop()
        elif self.cadence.is_coordinator:
            self._drain_flag = True

    def _await_proceed(self):
        """Coordinator trainer, post-snapshot: block for the learner's
        proceed decision (True = shutdown), or None when stop() forced the
        thread down with no verdict ever delivered.  Bounded by stop_event
        so a drain that bypasses the boundary cannot wedge the thread —
        but a verdict that was ALREADY delivered must still be returned:
        the learner's shutdown path is proceed(stop) immediately followed
        by stop(), and if stop_event winning that race swallowed the
        verdict, the final agree_stop broadcast would never be dispatched
        and every follower would sit abandoned inside the collective until
        the watchdog exits them 75 out of a CLEAN run."""
        while not self.stop_event.is_set():
            try:
                return self._proceed_queue.get(timeout=1.0)
            except queue.Empty:
                continue
        try:
            return self._proceed_queue.get_nowait()
        except queue.Empty:
            return None

    def _agreed_finish(self) -> None:
        """The stop/drain broadcast just returned on THIS rank — and, being
        a collective, on every other rank within the same dispatch: the run
        is coherently over everywhere.  Tell the learner so it disarms the
        health plane NOW, not at run() teardown — teardown skews ranks by
        arbitrary seconds (worker joins, final fetches), and an armed plane
        would misread the first rank's silence as a lost host (pinned by
        tests/test_health.py::test_disarm_silences_both_detectors)."""
        if self.on_agreed_finish is not None:
            self.on_agreed_finish()

    # -- cadence / watchdog plumbing -----------------------------------------

    def _wedge_forever(self) -> None:
        """HANDYRL_FAULT_WEDGE_PROCESS landed on this rank: simulate a
        frozen host — this thread never progresses and never exits."""
        print(
            "[fault] trainer wedged: no longer joining collectives "
            "(HANDYRL_FAULT_WEDGE_PROCESS)",
            file=sys.stderr,
        )
        while True:
            time.sleep(60.0)

    def _arm(self, tag: str) -> None:
        wd = self.collective_watchdog
        if wd is not None and self._collective_dispatched:
            # first-ever dispatch pays jit compilation — the heartbeat
            # plane covers pre-first-step peer deaths (compile-grace,
            # same rationale as the plane watchdog's)
            wd.arm(tag)

    def _disarm(self) -> None:
        wd = self.collective_watchdog
        if wd is not None:
            wd.disarm()

    def _agree_step(self, stepped: bool) -> int:
        """One cadence broadcast per loop iteration (multi-process only):
        returns the agreed command.  The coordinator's epoch-end verdict
        mirrors the single-process loop condition (update_flag armed and
        at least one step taken); the DRAIN bit rides the same broadcast."""
        if self._fault_wedge_process:
            self._wedge_forever()
        from ..parallel.distributed import CMD_DRAIN

        self._arm("cadence agree_step")
        try:
            cmd = self.cadence.agree_step(
                end=stepped and self.update_flag, drain=self._drain_flag
            )
        finally:
            self._disarm()
        if cmd & CMD_DRAIN:
            self.drain_agreed = True
        return cmd

    def _warmed_up(self) -> bool:
        """Epoch boundaries before the warmup threshold return immediately
        (reference train.py:343-346); device-replay mode counts ingested
        episodes (the store is bypassed)."""
        if self.device_replay is not None:
            return self.device_replay.counters["episodes"] >= self.args["minimum_episodes"]
        return len(self.store) >= self.args["minimum_episodes"]

    def _maybe_publish_params(self) -> None:
        """Split plane only: push a versioned replicated param copy onto
        the actor mesh once param_refresh_updates steps have passed since
        the last publish.  Runs on the SGD thread between dispatches, so
        ``self.state["params"]`` is the just-returned state's — valid
        until the NEXT train step donates it, and the cross-mesh copy
        dispatched here holds its own buffer reference."""
        cache = self.param_cache
        if cache is not None and self.steps - cache.version >= self.param_refresh:
            cache.publish(self.state["params"], self.steps)

    def _step_lr(self, lr: float, k: int) -> float:
        """The lr for the next k-step dispatch, with the NaN fault window
        applied (HANDYRL_FAULT_NAN_AT_STEP): a NaN anywhere in the update
        chain is what the in-step sentinel must catch."""
        w = self._fault_nan
        if w is not None:
            start, count = w
            if self.steps < start + count and self.steps + k > start:
                return float("nan")
        return lr

    def _maybe_fault_sigterm(self) -> None:
        """HANDYRL_FAULT_SIGTERM_AT_STEP: deliver a preemption mid-epoch."""
        if (
            self._fault_sigterm is not None
            and not self._fault_sigterm_fired
            and self.steps >= self._fault_sigterm
        ):
            self._fault_sigterm_fired = True
            print(
                f"[fault] SIGTERM at step {self.steps} "
                "(HANDYRL_FAULT_SIGTERM_AT_STEP)",
                file=sys.stderr,
            )
            os.kill(os.getpid(), signal.SIGTERM)

    def _sentinel_account(self, fetched: List[Dict[str, Any]]) -> int:
        """Epoch-end sentinel bookkeeping over the fetched per-dispatch
        metrics (no extra device syncs: these values were coming to host
        anyway).  In-step skip flags and host-detected loss spikes extend
        one consecutive-bad streak; a clean dispatch resets it.  Skipped
        and spiked dispatches never feed the EMA — a diverging loss must
        not drag the detector's baseline up after it.  Returns the number
        of in-step-SKIPPED steps this epoch (their dcnt was zeroed, so the
        caller must exclude them from the lr schedule's per-step data-count
        average too)."""
        skipped = 0
        for m in fetched:
            bad = int(round(float(m.get("sentinel_bad", 0.0))))
            if bad:
                skipped += bad
                self.sentinel_events["sentinel_skipped_steps"] += bad
                self._sentinel_streak += bad
                continue
            dcnt = float(m["dcnt"])
            if dcnt <= 0:
                continue
            loss = abs(float(m["total"])) / dcnt
            if (
                self._loss_ema is not None
                and loss > self._spike_factor * max(self._loss_ema, 1e-8)
            ):
                self.sentinel_events["sentinel_spike_steps"] += self.fused
                self._sentinel_streak += self.fused
                continue
            self._sentinel_streak = 0
            d = self._loss_ema_decay
            self._loss_ema = (
                loss if self._loss_ema is None else d * self._loss_ema + (1 - d) * loss
            )
        if self._sentinel_streak >= self.sentinel_rollback_after:
            self._sentinel_rollback()
        return skipped

    def _sentinel_rollback(self) -> None:
        """Roll the train state back to the newest VERIFIED manifest
        checkpoint (PR 2's machinery): params from the snapshot, a fresh
        optimizer (the moments fed the divergence), the step counter kept
        MONOTONE (lr schedule, param-cache publish versions and the host
        books all key off it), and the device-replay sampling RNG
        re-seeded past the poison window.  No verified snapshot (or a
        corrupt manifest) keeps the current params — the in-step skip
        already prevents poisoning, so continuing is safe — and resets
        the streak so the decision is re-evaluated on fresh evidence."""
        from . import checkpoint as ckpt

        self._sentinel_streak = 0
        self._loss_ema = None
        model_dir = self.args.get("model_dir", "models")
        if self.cadence is not None:
            # cross-process coherence: the streak that got us here is
            # computed from the COLLECTIVE step metrics, so every rank is
            # in this call together — but only the coordinator owns the
            # checkpoint files.  Its manifest verdict AND the snapshot
            # params themselves ride broadcasts, so all ranks roll back
            # to the SAME manifest entry (or all keep params) and stay
            # bit-identical; a follower scanning its own (possibly empty)
            # model_dir would silently diverge.
            from ..parallel.distributed import broadcast_params

            local_epoch = 0
            if self.cadence.is_coordinator:
                try:
                    local_epoch = ckpt.latest_verified_epoch(model_dir)
                except ckpt.CheckpointError as exc:
                    print(
                        f"[sentinel] rollback wanted but the manifest is "
                        f"corrupt ({exc}); keeping current params on every "
                        "process",
                        file=sys.stderr,
                    )
            self._arm("sentinel rollback agreement")
            try:
                epoch = self.cadence.agree_rollback_epoch(local_epoch)
            finally:
                self._disarm()
            if epoch <= 0:
                print(
                    "[sentinel] divergence streak hit the rollback "
                    "threshold but the coordinator has no verified "
                    "snapshot; keeping current params (in-step skips "
                    "already suppressed the bad updates)",
                    file=sys.stderr,
                )
                return
            if self.cadence.is_coordinator:
                params = ckpt.load_verified_params(
                    model_dir, epoch, self.state_host["params"],
                    pre_verified=True,
                )
            else:
                # like-shaped input; values replaced by the broadcast
                params = self.state_host["params"]
            self._arm("sentinel rollback params broadcast")
            try:
                params = broadcast_params(params, self.ctx.mesh)
            finally:
                self._disarm()
        else:
            try:
                epoch = ckpt.latest_verified_epoch(model_dir)
            except ckpt.CheckpointError as exc:
                print(
                    f"[sentinel] rollback wanted but the manifest is corrupt "
                    f"({exc}); keeping current params",
                    file=sys.stderr,
                )
                return
            if epoch <= 0:
                print(
                    "[sentinel] divergence streak hit the rollback threshold "
                    "but no verified snapshot exists yet; keeping current "
                    "params (in-step skips already suppressed the bad updates)",
                    file=sys.stderr,
                )
                return
            params = ckpt.load_verified_params(
                model_dir, epoch, self.state_host["params"], pre_verified=True
            )
        self.sentinel_events["sentinel_rollbacks"] += 1
        self._reset_state_from(params)
        print(
            f"[sentinel] rolled back to verified epoch {epoch} after a "
            f"divergence streak (step counter stays at {self.steps}; "
            "fresh optimizer; re-seeded sampling RNG)",
            file=sys.stderr,
        )

    def _reset_state_from(self, params) -> None:
        """The shared rollback tail: rebuild the train state around
        ``params`` with a fresh optimizer (the moments fed the problem),
        the step counter kept MONOTONE (lr schedule, param-cache publish
        versions and the host books all key off it), and the device-replay
        sampling RNG jumped far from the stream that fed the poison.
        Callers bump their event counter FIRST — the re-seed keys off the
        total rollback count."""
        # init_state dispatches multi-device layout programs; mid-run the
        # rollout thread may be dispatching concurrently — init_state now
        # takes the learner mesh's locks per program itself (the locks are
        # not reentrant, so wrapping it here again would deadlock)
        state = self.ctx.init_state(params)
        state["steps"] = jax.device_put(
            np.int32(self.steps), self.ctx._replicated
        )
        self.state = state
        # graftlint: allow[HS001] reason=rollback is a rare recovery path; the host snapshot is what checkpoints/drains read
        self.state_host = jax.device_get(state)
        self._replay_key = jax.random.PRNGKey(
            (self.args["seed"] ^ 0x7EA1)
            + 0x9E3779B9 * (
                self.sentinel_events["sentinel_rollbacks"]
                + self.sentinel_events["sentinel_flywheel_rollbacks"]
            )
            + self.steps
        )

    # -- quality-plane rollback (flywheel/quality.py signal) ------------------

    def request_rollback(self, epoch: int) -> None:
        """Ask for a rollback to verified ``epoch`` (<= 0 = newest
        verified).  Called from the learner's server thread when the
        serving tier's quality sentinel signals a regressed snapshot; the
        actual state reset happens at the next ``train_epoch`` entry on
        the trainer's own thread, so it can never race a device step the
        trainer is mid-way through dispatching."""
        self._requested_rollback = int(epoch)

    def _consume_requested_rollback(self) -> None:
        requested = self._requested_rollback
        if requested is None:
            return
        self._requested_rollback = None
        from . import checkpoint as ckpt

        if self.cadence is not None:
            # the collective path needs every rank in the call together
            # (agree + broadcast); a one-sided quality signal cannot drive
            # it safely — the divergence sentinel's collective machinery
            # remains the multi-process recovery story
            print(
                "[flywheel] quality rollback requested but a multi-process "
                "cadence is active; skipping the one-sided reset",
                file=sys.stderr,
            )
            return
        model_dir = self.args.get("model_dir", "models")
        try:
            target = requested if requested > 0 else \
                ckpt.latest_verified_epoch(model_dir)
            if target <= 0:
                print(
                    "[flywheel] quality rollback requested but no verified "
                    "snapshot exists; keeping current params",
                    file=sys.stderr,
                )
                return
            # full digest scan, not pre_verified: the signal names an epoch
            # the SERVING tier trusted — this process has not verified it
            params = ckpt.load_verified_params(
                model_dir, target, self.state_host["params"]
            )
        except ckpt.CheckpointError as exc:
            print(
                f"[flywheel] quality rollback to epoch {requested} refused "
                f"({exc}); keeping current params",
                file=sys.stderr,
            )
            return
        self._sentinel_streak = 0
        self._loss_ema = None
        self.sentinel_events["sentinel_flywheel_rollbacks"] += 1
        self._reset_state_from(params)
        print(
            f"[flywheel] rolled back to verified epoch {target} on the "
            f"serving tier's quality signal (step counter stays at "
            f"{self.steps}; fresh optimizer; re-seeded sampling RNG)",
            file=sys.stderr,
        )

    def train_epoch(self) -> Any:
        """Train until the learner flags an epoch end; return param snapshot."""
        self._consume_requested_rollback()
        batch_cnt, data_cnt = 0, 0
        metric_accum = []
        lr = self.lr
        wait_s = 0.0
        warmup_wait_s = 0.0
        t_epoch = time.perf_counter()
        fused = self.fused
        replay_train = None
        last_batch = None
        if self.device_replay is not None and self.cadence is None:
            # all-on-device SGD: sample + assemble + step in one dispatch.
            # One-deep pipelining (block on update N-1 before dispatching
            # N+1) keeps the dispatch queue shallow so the concurrent
            # rollout thread gets device time at every boundary.
            replay_train = train = self.device_replay.train_fn(self.ctx, fused)
            on_cpu = jax.default_backend() == "cpu"
            while data_cnt == 0 or not self.update_flag:
                if self.stop_event.is_set():
                    break
                self._replay_key, sub = jax.random.split(self._replay_key)
                with trace_span("train_step", plane="learner"):
                    self.state, metrics = train(
                        self.state, sub, self._step_lr(lr, fused)
                    )
                if metric_accum:
                    with trace_span("train.pipeline_block", plane="learner"):
                        # graftlint: allow[HS001] reason=deliberate one-deep pipelining: block on update N-1 so the dispatch queue stays shallow and the concurrent rollout thread gets device time
                        jax.block_until_ready(metric_accum[-1]["total"])
                metric_accum.append(metrics)
                batch_cnt += fused
                self.steps += fused
                self._maybe_publish_params()
                self._maybe_fault_sigterm()
                data_cnt = 1
                if on_cpu:
                    # On the CPU backend dispatch_serialized blocks INSIDE
                    # the dispatch lock, and this loop re-acquires it
                    # microseconds after releasing — an unfair
                    # threading.Lock then starves the rollout thread
                    # indefinitely (observed: 35 min, zero episodes).  A
                    # real sleep hands the lock to the waiting producer;
                    # on TPU dispatch is async and the gap never forms.
                    time.sleep(0.02)
        elif self.device_replay is not None:
            # pod-slice rung 1 (docs/performance.md §Pod-slice topology):
            # per-process rings under the coordinator cadence.  The fused
            # all-on-device path above cannot run here — it would fuse a
            # process-LOCAL ring gather into the cross-host collective
            # program (the rings live on different local meshes per
            # process).  Instead each agreed iteration samples this
            # process's B/nprocs shard to host (one D2H of sampled rows)
            # and re-enters the collective mesh through put_batch's
            # make_array_from_process_local_data seam — so every device
            # dispatch (local sample AND collective step) happens inside
            # the agreed cadence window, never racing the lockstep
            # collectives.  The local sample holds a SUBSET of the global
            # step's device locks, so the per-device dispatch order stays
            # consistent across ranks.
            from ..parallel import local_batch_size
            from ..parallel.distributed import CMD_END

            B_local = local_batch_size(self.args["batch_size"])
            on_cpu = jax.default_backend() == "cpu"
            while True:
                # coordinator-broadcast epoch end: every process runs the
                # SAME step count, or the next collective wedges
                if self._agree_step(data_cnt > 0) & CMD_END:
                    break
                if self.stop_event.is_set():
                    if self.cadence.is_coordinator:
                        # end the epoch THROUGH the cadence (see the host
                        # branch's batch-None path: a bare break abandons
                        # the broadcast the followers are blocked in)
                        self._drain_flag = True
                        continue
                    break
                self._replay_key, sub = jax.random.split(self._replay_key)
                t0 = time.perf_counter()
                rows = self.device_replay.sample_host(sub, fused * B_local)
                if fused > 1:
                    # i.i.d. draws: slicing fused*B rows into k groups is
                    # equivalent to k independent B-row samples
                    batch = self.ctx.put_batches([
                        jax.tree.map(
                            lambda x, i=i: x[i * B_local:(i + 1) * B_local],
                            rows,
                        )
                        for i in range(fused)
                    ])
                else:
                    batch = self.ctx.put_batch(rows)
                sample_wait = time.perf_counter() - t0
                trace_event("batch.wait", sample_wait, plane="learner")
                if self._warmup_wait_pending:
                    self._warmup_wait_pending = False
                    warmup_wait_s = sample_wait
                else:
                    wait_s += sample_wait  # data-plane time (north-star)
                last_batch = batch  # batches aren't donated; safe to re-lower
                step_lr = self._step_lr(lr, fused)
                self._arm("train_step @ step %d" % self.steps)
                try:
                    with trace_span("train_step", plane="learner"):
                        if fused > 1:
                            self.state, metrics = self.ctx.train_steps(self.state, batch, step_lr)
                        else:
                            self.state, metrics = self.ctx.train_step(self.state, batch, step_lr)
                finally:
                    self._disarm()
                self._collective_dispatched = True
                metric_accum.append(metrics)
                batch_cnt += fused
                self.steps += fused
                self._maybe_publish_params()
                self._maybe_fault_sigterm()
                data_cnt = 1
                if on_cpu:
                    # same rollout-thread fairness as the fused path: the
                    # local sample re-takes the actor-overlapping dispatch
                    # locks every iteration on the CPU backend
                    time.sleep(0.02)
        else:
            from ..parallel.distributed import CMD_END

            while True:
                if self.cadence is not None:
                    # coordinator-broadcast epoch end: every process runs
                    # the SAME step count, or the next collective wedges
                    if self._agree_step(data_cnt > 0) & CMD_END:
                        break
                elif data_cnt > 0 and self.update_flag:
                    break
                t0 = time.perf_counter()
                batch = self.batcher.batch()
                batch_wait = time.perf_counter() - t0
                # already-measured duration -> span (no second clock read
                # on the disabled path; trace_event is a no-op there)
                trace_event("batch.wait", batch_wait, plane="learner")
                if self._warmup_wait_pending:
                    # first batch of the RUN: the wait covers the assembly
                    # plane's one-off warm-up, and the first train dispatch
                    # right after it pays the jit compile — neither is
                    # steady-state input starvation, so it must not sit in
                    # the north-star input_wait_frac (it lands in its own
                    # input_wait_warmup_s stat instead)
                    self._warmup_wait_pending = False
                    warmup_wait_s = batch_wait
                else:
                    wait_s += batch_wait  # input starvation (north-star)
                if batch is None:  # shutting down
                    if (
                        self.cadence is not None
                        and self.cadence.is_coordinator
                    ):
                        # the stop landed while this rank was starved in
                        # batch() (forced drain-deadline shutdown): end
                        # the epoch THROUGH the cadence — a bare break
                        # would abandon the broadcast the followers are
                        # (or will be) blocked in, stranding them on the
                        # collective watchdog's full timeout.  This holds
                        # even when _drain_flag is ALREADY set: reaching
                        # here proves the bit never rode a broadcast (a
                        # broadcast DRAIN breaks the loop at agree_step,
                        # before batch() runs again), so the next loop-top
                        # iteration is the one that finally sends END|DRAIN.
                        # The watchdog armed around that broadcast still
                        # bounds this rank if the peers are already gone.
                        self._drain_flag = True
                        continue
                    break
                last_batch = batch  # batches aren't donated; safe to re-lower
                step_lr = self._step_lr(lr, fused)
                self._arm("train_step @ step %d" % self.steps)
                try:
                    with trace_span("train_step", plane="learner"):
                        if fused > 1:  # k updates per device call, metrics pre-summed
                            self.state, metrics = self.ctx.train_steps(self.state, batch, step_lr)
                        else:
                            self.state, metrics = self.ctx.train_step(self.state, batch, step_lr)
                finally:
                    self._disarm()
                self._collective_dispatched = True
                metric_accum.append(metrics)
                batch_cnt += fused
                self.steps += fused
                self._maybe_publish_params()
                self._maybe_fault_sigterm()
                data_cnt = 1  # real count resolved below without device sync per step
        if not metric_accum:
            return self.state_host["params"]

        self._arm("epoch-end metrics fetch")
        try:
            with trace_span("epoch.metrics_fetch", plane="learner"):
                # graftlint: allow[HS001] reason=epoch-end fetch of the whole epoch's metrics in one device_get — once per epoch, not per dispatch
                fetched = jax.device_get(metric_accum)
        finally:
            self._disarm()
        skipped_steps = 0
        if self.sentinel:
            # skip flags + spike detection + (possibly) rollback — all on
            # values already fetched for the loss report, no extra syncs
            skipped_steps = self._sentinel_account(fetched)
        data_cnt = float(sum(m["dcnt"] for m in fetched))
        loss_sum = {
            k: float(sum(m[k] for m in fetched))
            for k in fetched[0]
            if k not in ("dcnt", "sentinel_bad")
        }
        self.last_loss = {k: v / max(data_cnt, 1) for k, v in loss_sum.items()}
        print("loss = %s" % " ".join(f"{k}:{v:.3f}" for k, v in self.last_loss.items()))
        elapsed = max(time.perf_counter() - t_epoch, 1e-9)
        self.stats = {
            "train_steps_per_sec": batch_cnt / elapsed,
            "input_wait_frac": wait_s / elapsed,
        }
        if warmup_wait_s:
            # one-off, first trained epoch only: the pipeline warm-up wait
            # excluded from input_wait_frac above
            self.stats["input_wait_warmup_s"] = round(warmup_wait_s, 4)
        if self.sentinel:
            # cumulative, like pipe_batcher_*: a nonzero value anywhere in
            # the run means the sentinel fired at some point
            for key, value in self.sentinel_events.items():
                self.stats[key] = value
        if self.param_cache is not None:
            # realized actor-plane staleness at the boundary (cumulative
            # refresh count rides along so soaks can spot a stalled flow)
            self.stats["plane_param_lag"] = self.param_cache.lag(self.steps)
            self.stats["plane_param_refreshes"] = self.param_cache.refreshes
        if self.device_replay is None:
            # per-epoch pipeline stage breakdown (cumulative counters
            # diffed against the previous epoch's snapshot) — attributes
            # any input_wait_frac to sample / assemble / queueing / put
            cur = self.batcher.stats()
            prev = self._pipe_stats0
            for key in PIPE_STAT_KEYS:
                self.stats["pipe_" + key] = round(
                    cur.get(key, 0.0) - prev.get(key, 0.0), 4
                )
            for key in PIPE_EVENT_KEYS:
                # cumulative, not diffed: any nonzero value flags that the
                # assembly plane took a fault at some point this run
                self.stats["pipe_" + key] = cur.get(key, 0.0)
            gets = cur.get("gets", 0.0) - prev.get("gets", 0.0)
            if gets > 0:
                self.stats["pipe_device_queue_depth"] = round(
                    (cur.get("device_queue_depth_sum", 0.0)
                     - prev.get("device_queue_depth_sum", 0.0)) / gets, 3
                )
            self._pipe_stats0 = cur
        peak = self._peak_flops()
        if peak:
            # resolution happens AFTER `elapsed` is taken: a multi-second
            # lowering must not deflate the first epoch's rate stats
            if self._flops_per_update is None:
                self._flops_per_update = self._resolve_flops(
                    replay_train, last_batch
                )
            if self._flops_per_update:
                self.stats["mfu"] = round(
                    self._flops_per_update * batch_cnt
                    / (elapsed * peak * self.ctx.mesh.size),
                    6,
                )
        # skipped steps zeroed their dcnt contribution, so they must not
        # sit in the divisor either — a NaN spell would otherwise silently
        # depress the lr schedule's per-step data-count average (an
        # all-skipped epoch leaves the EMA untouched: no evidence)
        applied_cnt = batch_cnt - skipped_steps
        if applied_cnt > 0:
            self.data_cnt_ema = (
                self.data_cnt_ema * 0.8 + data_cnt / (1e-2 + applied_cnt) * 0.2
            )
        # graftlint: allow[HS001] reason=epoch-boundary host snapshot: the device state is donated every step, so checkpoint/publish readers need this copy
        self.state_host = jax.device_get(self.state)
        return self.state_host["params"]

    def _peak_flops(self) -> Optional[float]:
        """Peak FLOP/s of one of the step's devices.  Utilization is a
        statement about an accelerator: a CPU run records none (None),
        and an accelerator whose kind has no peak in the table is an
        error, not a silently missing stat."""
        from ..parallel.train_step import peak_flops_per_chip

        device = self.ctx.mesh.devices.flat[0]
        return None if device.platform == "cpu" else peak_flops_per_chip(device)

    def _resolve_flops(self, replay_train, batch) -> float:
        """One-time FLOPs-per-update resolution at the end of the first
        trained epoch (a lowering / trace, nothing executes); 0.0 when
        the epoch trained nothing to trace."""
        if replay_train is not None:
            return float(replay_train.flops_per_update(self.state))
        if batch is None:
            return 0.0
        if self.fused > 1:
            # stacked (k, B, ...) tree -> one batch of AVALS: a concrete
            # x[0] slice would dispatch multi-device gathers outside the
            # per-device dispatch locks (the serialized-dispatch
            # invariant, parallel/mesh.py); the lowering only needs shapes
            batch = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), batch
            )
        return float(self.ctx.flops_per_step(self.state, batch))

    @property
    def failed(self) -> bool:
        """The training plane went down on its own: run() or a pipeline
        thread raised (traceback on stderr) and set the stop event, which
        otherwise only stop() sets.  The learner raises on it — a run
        whose trainer died must not finish its epochs untrained."""
        return self.stop_event.is_set() and not self._stop_requested

    def stop(self):
        self._stop_requested = True
        self.stop_event.set()
        # every pipeline joins what it started (process batchers also
        # unlink their shm)
        self.batcher.stop()

    def run(self):
        try:
            self._run()
        except BaseException:
            self.stop_event.set()  # -> failed; the learner raises on it
            raise

    def _run(self):
        print("waiting training")
        while not self._warmed_up():
            if self.stop_event.is_set():
                return
            time.sleep(1)
        if self.device_replay is None:
            self.batcher.start()
        print("started training")
        profile_dir = self.args.get("profile_dir")
        tracing = False
        if profile_dir:
            # capture the first trained epoch (SURVEY.md §5.1: the reference
            # has no tracing at all; here it's one config key away)
            jax.profiler.start_trace(profile_dir)
            tracing = True
        try:
            while not self.stop_event.is_set():
                params = self.train_epoch()
                if tracing:
                    jax.profiler.stop_trace()
                    print(f"wrote profiler trace to {profile_dir}")
                    tracing = False
                self.update_flag = False
                if self.cadence is not None:
                    self._awaiting_proceed = True
                self.update_queue.put((params, self.steps))
                if self.cadence is not None:
                    if self.drain_agreed:
                        # agreed preemption drain: no further collectives;
                        # every process leaves the loop at this boundary
                        self.finished = True
                        self._agreed_finish()
                        return
                    # the coordinator waits for its learner's shutdown
                    # decision, then broadcasts it; followers join the
                    # broadcast directly — all trainers stop (or start the
                    # next epoch) together.  The coordinator skips the
                    # broadcast ONLY when no verdict was ever delivered
                    # (forced stop mid-drain): a delivered verdict is
                    # always broadcast even if stop() already landed,
                    # because the followers are (or will be) blocked in
                    # this collective waiting for it.
                    if self.cadence.is_coordinator:
                        stop_local = self._await_proceed()
                        self._awaiting_proceed = False
                        if stop_local is None:
                            return
                        # only the coordinator arms the boundary stop: a
                        # follower reaches this collective right after its
                        # queue put, but the coordinator joins only after
                        # its learner's boundary work (eval feed, verified
                        # checkpoint write, snapshot GC) — at production
                        # sizes that legitimately exceeds the collective
                        # bound, and an armed follower would exit 75 out
                        # of a healthy fleet.  A coordinator that dies in
                        # that window is the heartbeat plane's catch.
                        self._arm("cadence agree_stop")
                    else:
                        stop_local = False
                        self._awaiting_proceed = False
                        if self.stop_event.is_set():
                            # follower forced down locally (drain deadline
                            # past): it cannot drive the cadence; peers
                            # escape through the collective watchdog
                            return
                    try:
                        stop = self.cadence.agree_stop(stop_local)
                    finally:
                        self._disarm()
                    if stop:
                        self.finished = True
                        self._agreed_finish()
                        return
        finally:
            if tracing:  # interrupted mid-first-epoch: still flush the trace
                jax.profiler.stop_trace()
                print(f"wrote profiler trace to {profile_dir}")
