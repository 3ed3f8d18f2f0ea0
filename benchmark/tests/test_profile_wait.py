"""The traced loop run and its profile: the main thread waits for the
watcher thread inside ``harness.stop_profile`` for as long as the run may
live, and says so by name where that was not enough.  The tiny loop cell of
test_rehearsal.py, traced, on the CPU, in this process, so that
``stop_profile`` and the run's deadline can be patched; no test sleeps out
a real limit.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_profile_wait.py -q
"""

import faulthandler
import json
import os
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import test_rehearsal as rehearsal  # noqa: E402
from benchmark import harness  # noqa: E402
from benchmark import run as entry  # noqa: E402

root = rehearsal.root     # the fixture: a benchmark root of tiny cells


@pytest.fixture()
def rehearse(root, monkeypatch, capsys):
    """Run the tiny loop cell traced through run.py's ``main`` with
    ``harness.stop_profile`` replaced; gives (exit code, the run, the
    diagnostic line, the result line)."""
    seen, release = {}, threading.Event()

    def go(stop_profile):
        def patched(run):
            seen["run"] = run
            stop_profile(run, release)
        monkeypatch.setattr(harness, "stop_profile", patched)
        code = entry.main(["--root", root, "--workload", "tiny_loop", "--seed", "3",
                           "--seconds", "8", "--trace", "1", "--rehearse"])
        lines = capsys.readouterr().out.strip().splitlines()
        return code, seen["run"], json.loads(lines[-2]), json.loads(lines[-1])

    yield go
    faulthandler.cancel_dump_traceback_later()     # main armed it in this process
    release.set()
    for thread in threading.enumerate():
        if thread.name == "bench-watcher":
            thread.join(timeout=60)
            assert not thread.is_alive()


real_stop = harness.stop_profile


def test_a_stop_longer_than_the_old_wait_is_waited_for(rehearse):
    """The old rule gave the watcher 30 of the run's 330 s.  In the same
    proportion: 11 s are left for the wait, the old rule's share of them is
    1 s, the stop takes 3 s.  The wait follows from what is left of the
    run, so the profile is there."""
    def slow_stop(run, release):
        run.deadline = time.monotonic() + harness.PROFILE_RESERVE_S + 11.0
        time.sleep(3.0)
        real_stop(run)

    code, run, earlier, last = rehearse(slow_stop)
    assert code == entry.EXIT_REHEARSAL
    assert run.xplane and os.path.getsize(run.xplane) == earlier["notes"]["profile_bytes"]
    assert earlier["checks"]["profile_collected"] is True
    assert "profile_not_collected" not in earlier["notes"]
    # what sizes the next failure is on the line
    notes = earlier["notes"]
    assert notes["profile_stop_s"] > 0 and notes["reduce_s"] >= 0
    assert notes["updates_in_trace"] == earlier["counters"]["updates"] > 0
    assert notes["bytes_per_update"] == pytest.approx(
        notes["profile_bytes"] / notes["updates_in_trace"])
    assert 0 < notes["seconds_left"] < harness.RUN_LIMIT_S
    assert set(notes["profile_planes"]) >= {"/host:CPU"}
    # the traced window holds two epoch boundaries at least
    assert earlier["counters"]["epochs"] >= 2


def test_a_stop_that_never_returns_fails_by_name(rehearse):
    """The watcher is still inside ``stop_profile`` when the run's time is
    up but for the reserve: ``profile_collected`` is false, the note says
    how long the wait was, and the run still ends with its two lines."""
    def stuck_stop(run, release):
        run.notes["profile_window_s"] = time.monotonic() - run.profile_t0
        # the learner takes a few of these seconds to stop; the rest is the wait
        run.deadline = time.monotonic() + harness.PROFILE_RESERVE_S + 12.0
        release.wait()
        real_stop(run)          # the test is over: close the session

    code, run, earlier, last = rehearse(stuck_stop)
    assert code == entry.EXIT_REHEARSAL and last["correct"] is False
    assert run.xplane is None
    assert earlier["checks"]["profile_collected"] is False
    note = earlier["notes"]["profile_not_collected"]
    assert 0.5 < note["waited_s"] < 12.0
    assert note["seconds_left"] == pytest.approx(harness.PROFILE_RESERVE_S, abs=0.5)
    assert note["stop_began"] is True and note["profile_bytes"] == 0
    assert "reduce_s" not in earlier["notes"]
    # the rest of the run is whole: the reference check ran, and the
    # three readers that need no profile answered
    assert earlier["checks"]["matches_reference"] is True
    assert earlier["notes"]["metrics_answered"] == [
        "rollout_wait_share", "setup_compile_s", "train_mfu"]
