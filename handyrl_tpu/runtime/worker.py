"""Actor workers and the local worker pool.

Topology vs the reference (handyrl/worker.py:26-189): the reference forks
Gather processes each owning ~16 Worker processes doing batch-1 torch-CPU
inference.  Here actors are *threads* sharing one device model through the
batched inference engine — the env step is cheap host python (no GIL
problem: the heavy part releases it inside XLA), and cross-env batching is
exactly what the TPU wants.  The remote path (TCP workers on other
machines, worker.py:192-271) plugs the same Worker loop into a socket
connection instead of a direct callable.

Protocol parity (worker.py:66-87): workers ask ``('args', None)``, run one
generation or evaluation job, and report ``('episode', ep)`` /
``('result', res)``.  Model ids: 0 = random model, -1 = latest, epoch
numbers otherwise.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..envs import make_env, prepare_env
from ..models import InferenceModel, RandomModel, init_variables
from .evaluation import Evaluator
from .generation import Generator
from .inference_engine import BatchedInferenceEngine


class LocalModelServer:
    """Serves model handles by id to in-process workers.

    The latest model lives behind ONE BatchedInferenceEngine shared by all
    actor threads; older epoch snapshots are loaded from disk on demand
    (reference train.py:604-614); id 0 is the zero-output RandomModel
    (reference worker.py:56-59).
    """

    def __init__(self, module, env, args: Dict[str, Any]):
        self.module = module
        self.args = args
        self.model_dir = args.get("model_dir", "models")
        variables = init_variables(module, env)
        self._model = InferenceModel(module, variables)
        env.reset()
        self._random = RandomModel.from_model(self._model, env.observation(env.players()[0]))
        self.engine = BatchedInferenceEngine(
            self._model, max_batch=args.get("inference_batch_size", 64)
        ).start()
        self.model_id = 0
        self._lock = threading.Lock()
        # cumulative count of requested snapshots served as LATEST instead
        # (missing / GC'd / corrupt file).  The substitution itself is the
        # right degradation — but an eval book quietly scored against the
        # wrong model must be VISIBLE, so the learner surfaces this in
        # metrics.jsonl as serve_snapshot_substituted
        self.substituted_snapshots = 0

    def publish(self, model_id: int, params) -> None:
        """Swap the served latest model (called by the learner per epoch)."""
        with self._lock:
            self._model = InferenceModel(self.module, {"params": params})
            self.engine.update_model(self._model)
            self.model_id = model_id

    def latest_params(self):
        return self._model.variables["params"]

    def latest_snapshot(self):
        """(model_id, params) read atomically — callers caching per id must
        not pair a stale id with newer params published in between."""
        with self._lock:
            return self.model_id, self._model.variables["params"]

    def stop(self) -> None:
        """Release the serving plane (Learner teardown); subclasses with
        more resident machinery (the league's router engines) extend it."""
        self.engine.stop(join=30.0)

    def get(self, model_id: int):
        if model_id == 0:
            return self._random
        with self._lock:
            current = self.model_id
        if model_id < 0 or model_id >= current:
            return self.engine.client()
        # old snapshot from disk; rare (transient stale ids / explicit
        # evals).  Digest-verified: a bit-rotted old snapshot silently
        # deciding evaluation outcomes would poison the win-rate books.
        from .checkpoint import load_verified_params

        try:
            params = load_verified_params(
                self.model_dir, model_id, self.latest_params()
            )
            return InferenceModel(self.module, {"params": params})
        except Exception:
            # missing / GC'd / corrupt snapshot: serve latest instead —
            # counted, so a poisoned eval book shows up in metrics.jsonl
            with self._lock:
                self.substituted_snapshots += 1
            return self.engine.client()


class Worker:
    """One actor loop: ask for a job, run it, report (worker.py:66-87)."""

    def __init__(self, env, args: Dict[str, Any], conn: Callable, model_server: LocalModelServer, wid: int = 0):
        self.env = env
        self.args = args
        self.conn = conn  # callable (req, data) -> response
        self.model_server = model_server
        self.wid = wid
        self.generator = Generator(env, args)
        self.evaluator = Evaluator(env, args)

    def _gather_models(self, model_ids: Dict[int, int]) -> Dict[int, Any]:
        return {p: self.model_server.get(mid) for p, mid in model_ids.items()}

    def run(self) -> None:
        from .inference_engine import EngineStopped

        while True:
            try:
                args = self.conn("args", None)
            except (ConnectionResetError, BrokenPipeError, OSError):
                break  # transport gone (severed/stalled gather); exit cleanly
            if args is None:
                break
            role = args["role"]
            try:
                models = self._gather_models(args["model_id"])
                if role == "g":
                    episode = self.generator.execute(models, args)
                    self.conn("episode", episode)
                elif role == "e":
                    result = self.evaluator.execute(models, args)
                    self.conn("result", result)
            except EngineStopped:
                break  # learner shut the engine down mid-job; drain quietly
            except (ConnectionResetError, BrokenPipeError, OSError):
                break  # transport gone; nothing left to report to
            except Exception as exc:
                # a transient job failure (e.g. one bad XLA batch fanned out
                # to every engine waiter) must not kill the actor thread —
                # a dead thread shrinks the pool and hangs learner shutdown
                print(f"worker {self.wid} job failed: {type(exc).__name__}: {exc}")
                if role == "g":
                    self.conn("episode", None)  # keep the server's books consistent
                elif role == "e":
                    self.conn("result", None)


class LocalWorkerPool:
    """Thread-per-actor pool feeding the learner directly (no sockets).

    Replaces WorkerCluster's Gather/Worker process tree (worker.py:99-189):
    with the shared inference engine there is nothing to fan out — request
    batching happens at the engine, so workers talk straight to the
    learner's request handler.
    """

    def __init__(self, args: Dict[str, Any], handler: Callable, model_server: LocalModelServer):
        self.args = args
        self.handler = handler  # learner's (req, data) -> response
        self.model_server = model_server
        self.threads: List[threading.Thread] = []

    def run(self) -> None:
        env_args = self.args["env"]
        num_parallel = self.args["worker"]["num_parallel"]
        prepare_env(env_args)
        for wid in range(num_parallel):
            worker = Worker(
                make_env(env_args), self.args, self.handler, self.model_server, wid
            )
            t = threading.Thread(target=worker.run, daemon=True, name=f"actor-{wid}")
            t.start()
            self.threads.append(t)

    def join(self, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        for t in self.threads:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            t.join(remaining)
