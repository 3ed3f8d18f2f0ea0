"""The rollout plane alone (runtime/rollout_plane.py): its host is six
lambdas, its lanes and rings are fakes, no program is jitted.  The watchdog's
ladder is in tests/test_sentinel.py; the plane under a whole ``Learner`` in
tests/test_device_replay.py, test_plane.py and test_program_names.py."""

import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

from handyrl_tpu.runtime.rollout_plane import (
    WATCHDOG_EVENT_KEYS,
    RolloutPlane,
    vector_env_of,
)


class _Stream:
    def __init__(self):
        self.steps, self.drained = 0, False

    def step(self, params, span):
        with span:
            self.steps += 1
        return ("records",)

    def drain(self):
        self.drained = True


class _Replay:
    """Every ingest finishes two episodes of three steps; stats come back one
    dispatch late, as ``DeviceReplay.ingest_counted(defer=True)`` hands them."""

    def __init__(self):
        self.in_flight, self.drained = 0, False
        self.counters = {"game_steps": 0, "ingests": 0}

    def _stats(self, n):
        return {"episodes": 2 * n, "game_steps": 6 * n, "player_steps": 12 * n,
                "outcome_sum": np.array([1.0, -1.0]) * n, "outcome_sq_sum": 2.0 * n}

    def ingest_counted(self, records, defer):
        assert records == "records" and defer
        self.in_flight += 1
        if self.in_flight < 2:
            return None
        self.in_flight -= 1
        return self._stats(1)

    def flush_counted(self):
        n, self.in_flight = self.in_flight, 0
        if not n:
            return None
        left = self._stats(n)
        left["outcome_sum"] = float(left["outcome_sum"].sum())   # as flush_counted sums it
        return left

    def drain(self):
        self.drained = True


def _served_then(plane, after=0, then=None):
    """Answer every submit at once; with the ``after``-th, ``then()``: the
    loop is between dispatches there, nothing half booked."""
    def submit(kind, payload):
        plane.submitted.append((kind, payload))
        if len(plane.submitted) == after:
            then()
        fut = Future()
        fut.set_result(None)
        return fut

    plane._submit = submit


def _plane(live=lambda: True):
    """A plane at generation 1 over fakes; ``submitted`` collects what reaches
    the host, answered at once."""
    plane = object.__new__(RolloutPlane)
    plane.submitted = []
    _served_then(plane)
    plane._live = live
    plane._budget_met = lambda: False
    plane._snapshot = lambda: (3, "params")
    plane._steps = lambda: 0
    plane._gen, plane._progress_t, plane._dispatched = 1, 0.0, False
    plane._halt = threading.Event()
    plane._fault_wedge = None
    plane._param_cache = plane._record_xfer = plane._stats = None
    plane.venv = SimpleNamespace(num_players=2)
    plane.replay = _Replay()
    plane.stream = _Stream()
    plane._lanes = SimpleNamespace(stream=lambda key, commit: plane.stream)
    return plane


def _run(target, *args):
    thread = threading.Thread(target=target, args=args, daemon=True)
    thread.start()
    return thread


def _wait(cond, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    assert cond()


def test_a_superseded_generation_exits_at_its_next_liveness_check():
    plane = _plane()
    thread = _run(plane._replay_loop, None, 1)
    _wait(lambda: plane.stream.steps >= 3)
    plane._gen = 2                      # the watchdog restarted the thread
    thread.join(timeout=10.0)
    assert not thread.is_alive()
    assert plane._dispatched and plane._progress_t > 0.0
    # what it booked while it lived went to the host under the epoch that
    # generated it; the rings and the stream are the new generation's to drain
    kind, counts = plane.submitted[0]
    assert kind == "device_counts"
    assert counts == {"episodes": 2, "players": 2, "model_id": 3, "game_steps": 6,
                      "outcome_sum": 0.0, "outcome_sq_sum": 2.0}
    assert not plane.replay.drained and not plane.stream.drained


def test_a_submit_never_answered_beats_the_heart_and_gives_up_with_the_host():
    live = [True]
    plane = _plane(live=lambda: live[0])
    plane._submit = lambda kind, payload: Future()
    plane.PATIENCE_S = 0.02
    done = []
    thread = _run(lambda: done.append(plane._submit_and_wait("device_counts", {}, 1)))
    first = plane._progress_t
    _wait(lambda: plane._progress_t > first)    # waiting on the server is no stall
    second = plane._progress_t
    _wait(lambda: plane._progress_t > second)
    assert thread.is_alive()
    live[0] = False
    thread.join(timeout=10.0)
    assert done == [False]


def test_the_tail_of_a_shutdown_is_not_submitted():
    """A shutdown-time submission could push the host's episode count over
    the next boundary and conjure a spurious epoch out of the drain."""
    live = [True]
    plane = _plane(live=lambda: live[0])
    _served_then(plane, 3, lambda: live.__setitem__(0, False))
    thread = _run(plane._replay_loop, None, 1)
    thread.join(timeout=10.0)
    assert not thread.is_alive()
    assert plane.stream.steps == 4 and len(plane.submitted) == 3
    assert plane.replay.in_flight == 0              # the tail was fetched, not booked
    assert plane.replay.drained and plane.stream.drained


def test_the_tail_of_a_restart_is_submitted_under_its_oldest_epoch():
    epochs = iter(range(3, 1000))
    plane = _plane()
    plane._snapshot = lambda: (next(epochs), "params")
    _served_then(plane, 3, lambda: setattr(plane, "_gen", 2))
    thread = _run(plane._replay_loop, None, 1)
    thread.join(timeout=10.0)
    assert not thread.is_alive()
    # stats are one dispatch old: each report rides the epoch of the dispatch
    # it counts, the tail that of the dispatch still in flight
    assert [(c["episodes"], c["model_id"]) for _, c in plane.submitted] == [
        (2, 3), (2, 4), (2, 5), (2, 6)]
    assert not plane.replay.drained and not plane.stream.drained


def test_backpressure_yields_the_chip_and_still_beats():
    plane = _plane()
    plane._budget_met = lambda: True
    stats = SimpleNamespace(idle=0.0)
    stats.bump = lambda actor_idle_s: setattr(stats, "idle", stats.idle + actor_idle_s)
    assert plane._backpressure(stats) and plane._progress_t > 0.0
    assert stats.idle == pytest.approx(0.02)
    plane._budget_met = lambda: False
    assert not plane._backpressure(None)


def test_stop_joins_the_rollout_thread_and_the_watchdog():
    live = [True]
    plane = _plane(live=lambda: live[0])
    plane.args = {"plane_stall_timeout": 120.0}
    plane.topology, plane.gateway = "fused", None
    plane.events = {k: 0 for k in WATCHDOG_EVENT_KEYS}
    plane.thread = _run(plane._replay_loop, None, 1)
    plane._watchdog = _run(plane._watchdog_loop)    # ticks once a second
    _wait(lambda: plane.stream.steps >= 1)
    live[0] = False
    t0 = time.monotonic()
    plane.stop(30.0)
    assert time.monotonic() - t0 < 0.9              # woken, not waited out
    assert not plane.thread.is_alive() and not plane._watchdog.is_alive()
    assert plane.events == {k: 0 for k in WATCHDOG_EVENT_KEYS}


def test_the_books_and_the_epoch_stats_the_host_reads():
    plane = _plane()
    plane.topology, plane.gateway = "split", None
    plane.events = {k: 0 for k in WATCHDOG_EVENT_KEYS}
    plane.replay.counters = {"game_steps": 96, "ingests": 3}
    assert plane.books() == {
        "device_game_steps": 96, "device_rollout_dispatches": 3, "plane": "split",
        "plane_watchdog_stalls": 0, "plane_watchdog_restarts": 0,
        "plane_watchdog_degraded": 0,
    }
    assert plane.epoch_stats(2.0) == {}             # fused, no gateway: no plane_* keys
    snap = {"actor_busy_s": 1.0, "actor_idle_s": 0.5, "actor_dispatches": 4.0,
            "param_lag_sum": 6.0}
    plane._stats0 = {}
    plane._stats = SimpleNamespace(snapshot=lambda: dict(snap))
    plane._param_cache = SimpleNamespace(bytes_transferred=300)
    plane._record_xfer = SimpleNamespace(bytes_transferred=100)
    assert plane.epoch_stats(2.0) == {
        "plane_actor_busy_frac": 0.5, "plane_actor_idle_frac": 0.25,
        "plane_xfer_bytes_per_sec": 200.0, "plane_param_lag_mean": 1.5,
    }
    assert plane.epoch_stats(2.0) == {              # diffed: nothing since
        "plane_actor_busy_frac": 0.0, "plane_actor_idle_frac": 0.0,
        "plane_xfer_bytes_per_sec": 0.0,
    }


def _env(venv):
    return SimpleNamespace(vector_env=lambda: venv)


@pytest.mark.parametrize("venv, args, streaming, match", [
    (None, {}, False, "exposes no vector_env"),
    (SimpleNamespace(), {}, True, "STREAMING vector env"),
    (SimpleNamespace(record=None), {}, True, "6 not divisible by the 4 devices"),
    (SimpleNamespace(record=None), {"observation": True}, False, "observer views"),
])
def test_a_vector_env_the_lanes_cannot_run_is_refused_at_startup(venv, args, streaming, match):
    env = _env(venv) if venv is not None else SimpleNamespace()
    train_args = dict({"env": {"env": "Toy"}, "observation": False, "seed": 0}, **args)
    mesh = SimpleNamespace(shape={"dp": 4})
    with pytest.raises(ValueError, match=match):
        vector_env_of(env, train_args, mesh, 6, streaming)


def test_a_vector_env_that_fits_is_handed_back():
    venv = SimpleNamespace(record=None, observe_mask=None)
    train_args = {"env": {"env": "Toy"}, "observation": True, "seed": 0}
    mesh = SimpleNamespace(shape={"dp": 4})
    assert vector_env_of(_env(venv), train_args, mesh, 8, True) is venv
