"""Multi-host (multi-process) initialization + epoch cadence for the
gradient plane.

The reference scales out with its pickle/TCP worker tree only — its learner
is single-host (``nn.DataParallel``, reference train.py:340-341).  Here the
learner itself can span hosts: ``jax.distributed.initialize`` connects the
processes, ``jax.devices()`` then returns the GLOBAL device list, and the
same ``make_mesh``/``NamedSharding`` train step runs SPMD across hosts with
XLA routing collectives over ICI within a slice and DCN across slices
(SURVEY.md §2.5 gradient-plane prescription).

Config (``train_args.distributed``)::

    distributed:
      coordinator_address: "10.0.0.1:1234"   # host:port of process 0
      num_processes: 4
      process_id: 0                          # or set via PROCESS_ID env
      initialization_timeout: 300.0          # loud failure, never a hang
      heartbeat_interval: 5.0                # cross-host health plane
      heartbeat_timeout: 30.0                # (parallel/health.py)
      collective_timeout: 300.0
      health_port: 0                         # 0 = coordinator port + 1

Division of labor when initialized:

* every process executes the jitted train step (SPMD requires all
  processes to join every collective), feeding its local batch shard via
  ``jax.make_array_from_process_local_data``;
* only process 0 (``is_coordinator()``) writes checkpoints/metrics and
  serves models to the actor plane — the guards live in
  ``runtime/learner.py``;
* the EPOCH CADENCE is coordinator-driven (``DistributedCadence``): every
  process must run the exact same sequence of collectives, so "is this
  epoch over" / "does the run stop" / "are we draining" are themselves
  tiny broadcast collectives from process 0, never local decisions.
"""

from __future__ import annotations

import os
import socket
import sys
import time
from typing import Any, Dict, Optional

import jax
import numpy as np


def _timeout_error(process_id: int, num_processes: int, address: str,
                   timeout: float, last_exc: Optional[BaseException]) -> RuntimeError:
    return RuntimeError(
        f"jax.distributed.initialize could not connect process "
        f"{process_id}/{num_processes} to the coordinator at {address} "
        f"within initialization_timeout={timeout:.0f}s "
        f"(last error: {type(last_exc).__name__}: {last_exc}). "
        "Check that distributed.coordinator_address names a reachable "
        "host:port, that process 0 is up, and that every process agrees "
        "on num_processes."
    )


def _await_coordinator(address: str, deadline: float, process_id: int,
                       num_processes: int, timeout: float) -> None:
    """TCP pre-flight for non-coordinator ranks: wait (backoff-retry,
    bounded by the same deadline) until the coordinator port ACCEPTS a
    connection before handing off to ``jax.distributed.initialize``.

    This probe is what makes the dead-coordinator case a catchable loud
    error at all: on this jax, a follower whose RegisterTask RPC times
    out doesn't raise — the C++ coordination client LOG(FATAL)s and
    SIGABRTs the process, so a Python-side retry around ``initialize``
    never regains control.  The not-yet-up race (process 0 boots a beat
    later than the fleet) is absorbed by the same loop."""
    from .health import _split_address

    host, port = _split_address(address)
    backoff = 0.25
    last_exc: Optional[BaseException] = None
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise _timeout_error(
                process_id, num_processes, address, timeout, last_exc
            ) from last_exc
        try:
            sock = socket.create_connection(
                (host, port), timeout=min(remaining, 5.0)
            )
            sock.close()
            return
        except OSError as exc:
            last_exc = exc
            time.sleep(min(backoff, max(0.0, deadline - time.monotonic())))
            backoff = min(backoff * 2.0, 5.0)


def _reset_half_initialized_state() -> None:
    """Make a retry of ``jax.distributed.initialize`` REAL: jax assigns
    ``global_state.client`` (and the rank-0 service) *before*
    ``client.connect()``, so a failed connect leaves initialize poisoned —
    every later call raises ``'distributed.initialize should only be
    called once'`` instantly, the retry loop absorbs nothing, and that
    misleading message would be reported as the final cause.  shutdown()
    resets exactly those fields; if the never-connected client refuses a
    clean shutdown, clear them by hand."""
    try:
        jax.distributed.shutdown()
    except Exception:
        from jax._src.distributed import global_state

        global_state.client = None
        global_state.service = None


def init_distributed(dist_args: Optional[Dict[str, Any]]) -> int:
    """Initialize ``jax.distributed`` from config; returns the process index.

    A missing/empty ``coordinator_address`` means single-process — no-op,
    returns 0.  ``process_id`` may come from the config or the
    ``PROCESS_ID`` environment variable (per-host launchers usually inject
    the rank via env).

    A dead or mis-addressed coordinator must surface as a LOUD bounded
    error, never an indefinite startup hang: ``initialization_timeout``
    caps the whole attempt (passed through to ``jax.distributed
    .initialize``, which itself retries the connect internally), and a
    short backoff-retry loop absorbs the coordinator-not-yet-up race a
    fleet launcher hits when process 0 boots a beat later than the rest.
    """
    if not dist_args or not dist_args.get("coordinator_address"):
        return 0
    address = dist_args["coordinator_address"]
    num_processes = int(dist_args["num_processes"])
    process_id = dist_args.get("process_id")
    if process_id is None:
        process_id = int(os.environ.get("PROCESS_ID", "0"))
    process_id = int(process_id)
    timeout = float(dist_args.get("initialization_timeout") or 300.0)
    deadline = time.monotonic() + timeout
    if process_id != 0:
        # a dead coordinator inside initialize is a C++ SIGABRT, not an
        # exception — prove the port is up first, under the same budget
        _await_coordinator(address, deadline, process_id, num_processes, timeout)
    backoff = 1.0
    last_exc: Optional[BaseException] = None
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        try:
            jax.distributed.initialize(
                coordinator_address=address,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=dist_args.get("local_device_ids"),
                initialization_timeout=max(1, int(remaining)),
            )
            return jax.process_index()
        except Exception as exc:  # grpc surfaces several concrete types
            last_exc = exc
            _reset_half_initialized_state()
            if time.monotonic() + backoff >= deadline:
                break
            time.sleep(backoff)
            backoff = min(backoff * 2.0, 15.0)
    raise _timeout_error(
        process_id, num_processes, address, timeout, last_exc
    ) from last_exc


def shutdown_distributed() -> None:
    """Synchronized ``jax.distributed.shutdown`` after a clean run.

    The coordination service runs a shutdown BARRIER: a process that
    simply exits (atexit) while its peers are still draining trips the
    service's own heartbeat timeout and every survivor gets a fatal abort
    (SIGABRT) — a clean multi-process run must therefore shut the service
    down explicitly, at a point every process reaches within seconds of
    the others (train_main does, right after Learner.run()).  Best
    effort: a failed disconnect must not turn a finished run into a
    nonzero exit."""
    if jax.process_count() <= 1:
        return
    try:
        jax.distributed.shutdown()
    except Exception as exc:
        print(
            f"[handyrl_tpu] jax.distributed.shutdown failed "
            f"({type(exc).__name__}: {exc}); continuing exit",
            file=sys.stderr,
        )


def is_coordinator() -> bool:
    """True on the process that owns checkpoints, metrics, model serving."""
    return jax.process_index() == 0


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    return jax.process_index()


def local_batch_size(global_batch_size: int) -> int:
    """Per-process share of a global batch (SPMD data feeding)."""
    n = jax.process_count()
    if global_batch_size % n:
        raise ValueError(
            f"batch_size {global_batch_size} not divisible by {n} processes"
        )
    return global_batch_size // n


def broadcast_from_coordinator(value: int) -> int:
    """Broadcast one int32 from process 0 to every process (a tiny
    collective; all processes must call).  The primitive under both the
    auto-resume epoch agreement and the epoch cadence."""
    from jax.experimental import multihost_utils

    return int(multihost_utils.broadcast_one_to_all(np.int32(value)))


def broadcast_resume_epoch(local_epoch: int) -> int:
    """Every SPMD process must resume the SAME epoch, and only the
    coordinator's manifest scan is authoritative (it owns the checkpoint
    files): process 0 passes its ``latest_verified_epoch`` verdict, the
    rest pass anything — all return the coordinator's value.  Pinned by
    tests/test_multihost.py::test_resume_epoch_broadcast_two_process."""
    if jax.process_count() <= 1:
        return int(local_epoch)
    return broadcast_from_coordinator(int(local_epoch))


def broadcast_params(tree, mesh):
    """Broadcast a param pytree from process 0 to every process (all
    processes must call; followers pass a LIKE-SHAPED tree whose values
    are discarded).  The primitive under the cross-process sentinel
    rollback: only the coordinator owns checkpoint files, so the rolled-
    back params themselves ride a collective — every rank installs the
    SAME bytes without needing the snapshot on its filesystem."""
    from jax.experimental import multihost_utils

    from .mesh import dispatch_serialized

    # the broadcast ends in a host fetch on purpose (the received params
    # are installed host-side), so it lives inside the dispatch scope
    # like the cadence broadcasts
    return dispatch_serialized(
        lambda: jax.tree.map(
            np.asarray, multihost_utils.broadcast_one_to_all(tree)
        ),
        mesh,
    )


# -- coordinator-driven epoch cadence ----------------------------------------

# agree_step() command bits, broadcast from the coordinator: CONTINUE (0)
# keeps stepping; END closes the epoch on every process after the same
# step count; DRAIN (always with END) additionally ends the RUN at this
# boundary for a preemption-safe drain, skipping the stop agreement.
CMD_CONTINUE = 0
CMD_END = 1
CMD_DRAIN = 2


class DistributedCadence:
    """Lockstep epoch cadence for the multi-process ``Learner``.

    Under ``jax.distributed`` every train step is a cross-process
    collective, so all processes must execute the SAME number of steps per
    epoch and agree on shutdown — a process deciding locally (its own
    episode counts, its own ``update_flag``) would leave the others wedged
    in a collective forever.  The coordinator's decisions are therefore
    broadcast as one tiny int32 collective per step (``agree_step``) and
    one per epoch boundary (``agree_stop``); followers pass 0 and obey.

    All calls happen on the trainer thread, in identical program order on
    every process: per epoch ``[agree_step (train_step agree_step)* ,
    agree_stop?]`` — ``agree_stop`` is skipped by every process alike when
    the epoch ended with the DRAIN bit set.  Dispatches hold the mesh's
    device locks (``dispatch_serialized``) like every other program.
    """

    def __init__(self, mesh):
        self.mesh = mesh
        self.is_coordinator = is_coordinator()
        self.num_processes = process_count()

    def _agree(self, value: int, tag: str = "agree") -> int:
        from ..utils.trace import trace_span
        from .mesh import dispatch_serialized

        # broadcast_one_to_all returns a host value: the device_get is the
        # point of the call (the cadence decision must reach the host), so
        # it lives inside the dispatch scope like the CPU backend's other
        # blocking dispatches.  The span times the whole rendezvous: under
        # rank skew it IS the wait for the slowest process, which is the
        # cross-host stall the observability plane exists to attribute
        with trace_span("cadence." + tag, plane="cadence"):
            return dispatch_serialized(
                lambda: broadcast_from_coordinator(value), self.mesh
            )

    def agree_step(self, end: bool, drain: bool) -> int:
        """One per trainer-loop iteration: the coordinator passes its local
        epoch-end / drain verdicts, everyone receives the agreed command."""
        cmd = CMD_CONTINUE
        if self.is_coordinator and (end or drain):
            cmd = CMD_END | (CMD_DRAIN if drain else 0)
        return self._agree(cmd, "agree_step")

    def agree_stop(self, stop: bool) -> bool:
        """One per epoch boundary (unless the epoch drained): the
        coordinator passes its learner's continue/shutdown decision."""
        return bool(
            self._agree(1 if (self.is_coordinator and stop) else 0, "agree_stop")
        )

    def agree_rollback_epoch(self, epoch: int) -> int:
        """Sentinel-rollback agreement: the coordinator passes its
        manifest verdict (the newest verified epoch, 0 = none), followers
        pass anything — all receive the same target.  Every process
        reaches this call together because the streak that triggers it is
        computed from the COLLECTIVE step metrics (identical on all
        ranks)."""
        return self._agree(
            int(epoch) if self.is_coordinator else 0, "agree_rollback"
        )
