"""Cross-environment batched inference engine (the actor-side TPU path).

The reference runs batch-1 CPU inference inside every worker process
(handyrl/model.py:50-60 via generation.py:45) — fine for torch-CPU, fatal
for a TPU whose MXU wants large batches.  Here many host-side actor threads
share ONE device model: each submits its (obs, hidden) and blocks on a
future; a dispatcher thread drains the request queue, stacks observations
into a single padded batch, runs one jitted apply, and scatters results.

Static shapes: batches are padded to power-of-two buckets up to
``max_batch`` so XLA compiles a handful of shapes, not one per batch size.

Recurrent models: per-request hidden pytrees are stacked alongside the
observations; requests with ``hidden=None`` get the module's initial state
slice so one batch can mix fresh and mid-episode environments.
"""

from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

import numpy as np

from ..utils import tree_map, tree_stack


class EngineStopped(RuntimeError):
    """Raised to waiters when the engine is stopped with requests pending."""


def next_bucket(n: int, max_batch: int) -> int:
    """Smallest power-of-two >= n, capped at max_batch — the static batch
    shapes XLA compiles (shared with the serving plane's batcher)."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)


_next_bucket = next_bucket  # pre-serving-plane spelling


def stack_padded(obs_list, hid_list, bucket: int, hidden_template):
    """Pad to ``bucket`` rows and stack into one batch (shared by this
    engine and the serving batcher — the padding semantics are subtle and
    must not drift: pad rows REPLICATE real entries, because they must be
    valid observations/state or XLA's output for the live rows changes).
    ``hid_list`` entries of None take the module's initial-state template;
    a None ``hidden_template`` means a stateless model (no hidden batch).
    """
    obs_list = list(obs_list)
    obs_list += [obs_list[0]] * (bucket - len(obs_list))
    obs_batch = tree_stack(obs_list)
    hidden_batch = None
    if hidden_template is not None:
        hid_list = [h if h is not None else hidden_template for h in hid_list]
        hid_list += [hidden_template] * (bucket - len(hid_list))
        hidden_batch = tree_stack(hid_list)
    return obs_batch, hidden_batch


class BatchedInferenceClient:
    """Per-actor facade with the reference inference API (model.py:50-60)."""

    def __init__(self, engine: "BatchedInferenceEngine"):
        self._engine = engine

    def init_hidden(self, batch_dims=()):
        return self._engine.init_hidden(batch_dims)

    def inference(self, obs, hidden=None) -> Dict[str, Any]:
        return self._engine.submit(obs, hidden).result()

    def submit(self, obs, hidden=None) -> Future:
        """Async request entry — lets a caller queue several players'
        observations before blocking, so they land in one device batch."""
        return self._engine.submit(obs, hidden)


class BatchedInferenceEngine:
    """One device model serving many actor threads with batched inference."""

    def __init__(self, model, max_batch: int = 64, max_wait_ms: float = 2.0):
        self.model = model  # InferenceModel (numpy in/out, jitted apply)
        self.max_batch = max(1, max_batch)
        self.max_wait = max_wait_ms / 1000.0
        self._queue: queue.Queue = queue.Queue()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # orders submit vs stop: an item can only be enqueued while the
        # stop flag is provably unset, so exactly one party ever owns the
        # final drain (the serve thread when it exists, stop() otherwise)
        self._lifecycle = threading.Lock()
        self.batches_served = 0
        self.requests_served = 0

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "BatchedInferenceEngine":
        if self._thread is None:
            self._thread = threading.Thread(target=self._serve_loop, daemon=True)
            self._thread.start()
        return self

    def stop(self, join: float = 0.0) -> None:
        """``join``: seconds to wait for the serve thread to leave its last
        batch (a caller about to let the interpreter go: torn down with a
        daemon thread inside a jax call, the process aborts)."""
        with self._lifecycle:
            if self._stop.is_set():
                return  # idempotent; the first stop already arranged the drain
            self._stop.set()
            self._queue.put(None)  # wake the dispatcher
            thread = self._thread
        if thread is None:
            # never started: there is no serve thread to own the drain
            self._fail_pending()
        elif join > 0:
            thread.join(timeout=join)

    def _fail_pending(self) -> None:
        """Fail every queued request.  Called exactly once, by the drain
        owner: the serve loop after it observes stop (requests admitted
        before the flag flipped are drained there), or stop() itself when
        the engine never started."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                break
            if item is not None and not item[2].done():
                item[2].set_exception(EngineStopped("inference engine stopped"))

    def update_model(self, model) -> None:
        """Swap in new variables (same module); takes effect next batch."""
        self.model = model

    # -- client API ---------------------------------------------------------

    def init_hidden(self, batch_dims=()):
        return self.model.init_hidden(batch_dims)

    def client(self) -> BatchedInferenceClient:
        return BatchedInferenceClient(self)

    def submit(self, obs, hidden=None) -> Future:
        fut: Future = Future()
        with self._lifecycle:
            # check-and-enqueue is atomic against stop(): after stop flips
            # the flag (under this lock) no request can enter the queue, so
            # the drain owner's final sweep provably sees every waiter —
            # the old post-put "if stopped: re-drain" dance raced a second
            # submit into a queue nobody would ever drain again
            if self._stop.is_set():
                fut.set_exception(EngineStopped("inference engine stopped"))
                return fut
            self._queue.put((obs, hidden, fut))
        return fut

    # -- dispatcher ---------------------------------------------------------

    def _drain(self) -> List:
        """Block for the first request, then gather more up to max_batch."""
        first = self._queue.get()
        if first is None:
            return []
        requests = [first]
        deadline = time.monotonic() + self.max_wait
        while len(requests) < self.max_batch:
            timeout = deadline - time.monotonic()
            try:
                if timeout <= 0:
                    item = self._queue.get_nowait()
                else:
                    item = self._queue.get(timeout=timeout)
            except queue.Empty:
                break
            if item is None:
                break
            requests.append(item)
        return requests

    def _serve_loop(self) -> None:
        while not self._stop.is_set():
            requests = self._drain()
            if not requests:
                continue
            try:
                self._serve(requests)
            except Exception as exc:  # propagate to every waiter
                for _, _, fut in requests:
                    if not fut.done():
                        fut.set_exception(exc)
        # single-owner drain: requests enqueued before stop flipped the
        # flag (submit holds the lifecycle lock, so none land after) are
        # failed here, on the one thread that also consumed them live
        self._fail_pending()

    def _serve(self, requests: List) -> None:
        model = self.model
        n = len(requests)
        bucket = next_bucket(n, self.max_batch)
        obs_batch, hidden_batch = stack_padded(
            [r[0] for r in requests], [r[1] for r in requests],
            bucket, model.init_hidden(),
        )
        outputs = model.inference_batch(obs_batch, hidden_batch)
        outputs = tree_map(np.asarray, outputs)
        for i, (_, _, fut) in enumerate(requests):
            fut.set_result(tree_map(lambda x: x[i], outputs))

        self.batches_served += 1
        self.requests_served += n
