"""The readers of the loop's named programs and of the rollout thread's
waits, on a reduced trace and a span list whose answers are worked out by
hand; and once through the rehearsal, where the program writes the spans.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_layer_readers.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import test_rehearsal as rehearsal  # noqa: E402
from benchmark import harness  # noqa: E402
from handyrl_tpu.runtime import device_eval, device_replay, device_rollout  # noqa: E402



def _program(seconds, runs, whole_seconds=None, whole_runs=None):
    """A program of the reduced trace; no run cut unless said."""
    return {"seconds": seconds, "runs": runs,
            "whole_seconds": seconds if whole_seconds is None else whole_seconds,
            "whole_runs": runs if whole_runs is None else whole_runs}


# a 2 s window: the fused-4 train program, 0.33 s a run, ran 5 times for
# 1.6 s, the window opening 0.05 s into the first; the rollout twice for
# 0.06 s, the window closing 0.01 s into the second (another fingerprint);
# the ingest twice for 0.04 s
REDUCED = {
    "window_s": 2.0,
    "programs": {
        "jit_%s(111)" % device_replay.TRAIN_PROGRAM: _program(1.6, 5.0, 1.32, 4.0),
        "jit_%s(222)" % device_rollout.STREAM_PROGRAM: _program(0.05, 1.0),
        "jit_%s(333)" % device_rollout.STREAM_PROGRAM: _program(0.01, 1.0, 0.0, 0.0),
        "jit_%s(444)" % device_replay.INGEST_PROGRAM: _program(0.04, 2.0),
        "jit_%s(555)" % device_eval.EVAL_PROGRAM: _program(0.02, 4.0),
        # a whole-episode rollout is another program, not this one's
        "jit_%s(666)" % device_rollout.EPISODE_PROGRAM: _program(0.5, 1.0),
    },
}
BY_HAND = {
    "rollout_device_share": 3.0,          # 0.06 / 2.0: a share keeps the cut run's part
    "rollout_ms_per_dispatch": 50.0,      # 0.05 / 1: whole runs only
    "train_device_share": 80.0,           # 1.6 / 2.0
    "replay_train_device_ms": 82.5,       # 1.32 / (4 x 4): whole runs only
    "ingest_device_share": 2.0,           # 0.04 / 2.0
}
PROGRAM_OF = {
    "rollout_device_share": device_rollout.STREAM_PROGRAM,
    "rollout_ms_per_dispatch": device_rollout.STREAM_PROGRAM,
    "train_device_share": device_replay.TRAIN_PROGRAM,
    "replay_train_device_ms": device_replay.TRAIN_PROGRAM,
    "ingest_device_share": device_replay.INGEST_PROGRAM,
}
PER_RUN = ("rollout_ms_per_dispatch", "replay_train_device_ms")
READERS = sorted(
    name[:-3] for name in os.listdir(os.path.join(BENCH, "layer_metrics"))
    if name.endswith(".py") and name != "loop_program.py")


def _span(name, t0, dur, thread="device-rollout-1"):
    return {"name": name, "t_mono": t0, "dur_s": dur, "thread": thread, "rank": 0}


# the window is [100, 102): on the rollout thread 0.5 s of stats fetches
# (one of them half outside the window's end), 0.1 s of lock waits, 0.3 s of
# budget yields, 0.2 s handing counts over; the trainer's lock wait is
# another thread's
SPANS = [
    _span("rollout.dispatch", 100.0, 0.05), _span("dispatch.wait", 100.0, 0.04),
    _span("dispatch.run", 100.04, 0.01),
    _span("rollout.ingest", 100.05, 0.35), _span("dispatch.wait", 100.05, 0.06),
    _span("replay.stats_fetch", 100.12, 0.28),
    _span("rollout.dispatch", 100.5, 0.05),
    _span("rollout.ingest", 100.55, 0.15), _span("replay.stats_fetch", 100.58, 0.12),
    _span("rollout.submit", 100.7, 0.2), _span("rollout.budget_wait", 101.0, 0.3),
    _span("rollout.dispatch", 101.5, 0.05),
    _span("rollout.ingest", 101.8, 0.4), _span("replay.stats_fetch", 101.9, 0.2),
    _span("dispatch.wait", 100.2, 0.7, thread="trainer"),
    _span("train_step", 100.2, 0.8, thread="trainer"),
]


@pytest.fixture()
def run():
    made = harness.Run(BENCH, "geese_loop", seed=1, seconds=30, trace=True,
                       rehearse=True, t_process=0.0)
    made.reduced = json.loads(json.dumps(REDUCED))
    made.counters = {"fused_steps": 4}
    made.t_window, made.window_s = 100.0, 2.0
    made.spans = [dict(s) for s in SPANS]
    return made


def _read(run, name):
    return harness.load_module(run.path("layer_metrics", name + ".py")).read(run)


def test_the_cell_lists_the_six_and_each_has_a_reader(run):
    names = run.metric_names("per_layer")
    for name in list(BY_HAND) + ["rollout_wait_share", "epoch_stall_share"]:
        assert name in names
        assert os.path.exists(run.path("layer_metrics", name + ".py"))
    # the loop's programs are found by the program's constants alone
    assert "programs" not in run.cell


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_program_reader_gives_the_number_worked_by_hand(run, name):
    assert _read(run, name) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_program_reader_answers_none_without_its_program(run, name):
    run.reduced["programs"] = {
        k: v for k, v in run.reduced["programs"].items()
        if k.split("(")[0] != "jit_" + PROGRAM_OF[name]}
    # PR 22's names: three programs called jit_fn match no constant
    run.reduced["programs"]["jit_fn(777)"] = {"seconds": 1.0, "runs": 3.0}
    assert _read(run, name) is None
    run.reduced = None                      # an untraced run
    assert _read(run, name) is None


@pytest.mark.parametrize("name", PER_RUN)
def test_time_per_run_answers_none_where_no_run_is_whole(run, name):
    """A window shorter than one run of the program: its share of the
    window stands, a time per run does not."""
    for program in run.reduced["programs"].values():
        program["whole_seconds"] = program["whole_runs"] = 0.0
    assert _read(run, name) is None
    assert _read(run, "train_device_share") == pytest.approx(80.0)


def _without_constants(monkeypatch):
    for owner in (device_rollout, device_replay, device_eval):
        for constant in [c for c in vars(owner) if c.endswith("_PROGRAM")]:
            monkeypatch.delattr(owner, constant)


def test_program_reader_answers_none_for_a_program_without_the_constant(run, monkeypatch):
    """An older commit's program (bb0b4ad) has no such constant: no raise."""
    _without_constants(monkeypatch)
    for name in BY_HAND:
        assert _read(run, name) is None


@pytest.mark.parametrize("name", READERS)
def test_every_reader_answers_none_with_nothing_to_read(run, monkeypatch, name):
    """Both sides of a check run these files: on a program with no
    constants, a run with no spans, no counters and no reduced trace, a
    reader leaves its metric out and raises nothing."""
    _without_constants(monkeypatch)
    run.reduced, run.spans, run.counters, run.setup_compile = None, [], {}, None
    assert _read(run, name) is None
    assert run.notes == {}


def test_every_accessor_answers_none_with_nothing_to_read(run):
    """What a reader asks a run for, on a run that has nothing of it."""
    run.reduced = None                          # untraced
    assert run.program("train") is None and run.program_named("jit__step") is None
    assert run.scope("attn0") is None
    run.reduced = json.loads(json.dumps(REDUCED))   # traced; the cell lists no scopes
    assert "scopes" not in run.cell and run.scope("attn0") is None
    run.reduced["scopes"] = {"attn0": {"seconds": 0.5, "ops": 12.0}}
    assert run.scope("attn0") == {"seconds": 0.5, "ops": 12.0}
    assert run.scope("attn1") is None           # listed, and no op carries it
    # no flops file has a scope_work yet: a reader of a scope's roofline
    # share leaves its metric out
    assert run.scope_work() is None


def test_rollout_wait_share_by_hand(run):
    # stats fetches 0.28 + 0.12 + 0.1 (the last is cut at 102.0) and the
    # rollout thread's lock waits 0.04 + 0.06, over 2 s
    assert _read(run, "rollout_wait_share") == pytest.approx(100.0 * 0.6 / 2.0)
    noted = run.notes["rollout_thread"]
    assert noted["replay.stats_fetch_share"] == pytest.approx(25.0)
    assert noted["dispatch.wait_share"] == pytest.approx(5.0)
    assert noted["rollout.budget_wait_share"] == pytest.approx(15.0)
    assert noted["rollout.submit_share"] == pytest.approx(10.0)
    assert noted["dispatches"] == 3
    assert noted["dispatch_period_ms"] == pytest.approx(750.0)


def test_rollout_wait_share_answers_none_without_its_span(run):
    run.spans = [s for s in run.spans if s["name"] != "replay.stats_fetch"]
    assert _read(run, "rollout_wait_share") is None
    assert "rollout_thread" not in run.notes
    run.spans = []
    assert _read(run, "rollout_wait_share") is None


root = rehearsal.root     # the fixture: a benchmark root of tiny cells


def test_rehearsed_loop_answers_rollout_wait_share(root):
    """The tiny loop cell of test_rehearsal.py, traced, on the CPU: the
    program writes the spans, the runner keeps those in the window, the
    reader answers (it needs no device plane)."""
    proc = rehearsal._run(root, "tiny_loop", 1)
    assert proc.returncode == 4, proc.stderr[-4000:]
    earlier = json.loads(proc.stdout.strip().splitlines()[-2])
    assert "rollout_wait_share" in earlier["notes"]["metrics_answered"]
    noted = earlier["notes"]["rollout_thread"]
    assert noted["dispatches"] > 0
    assert 0.0 <= noted["replay.stats_fetch_share"] <= 100.0
