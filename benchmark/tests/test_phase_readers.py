"""The readers of the phases inside the device programs
(``update_step_share``, ``sample_step_share``, ``rollout_env_share``), of the
rollout thread's ``rollout.submit`` and of the packed step's padding: on a
reduced fixture worked out by hand, on a synthetic trace that stands for the
profile's second pass, on the v5e capture the repo keeps, and through the
rehearsals, where the CPU has no device plane to read.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_phase_readers.py -q
"""

import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import test_hybrid_rehearsal as hybrid  # noqa: E402
import test_layer_readers as layer_readers  # noqa: E402
import test_rehearsal as rehearsal  # noqa: E402
from benchmark import harness, trace_reduce  # noqa: E402
from handyrl_tpu.parallel import train_step  # noqa: E402
from handyrl_tpu.runtime import device_replay, device_rollout  # noqa: E402

ALL_CELLS = ["geese_loop", "xfmr_train_t64", "xfmr_train_t64_dp4",
             "nemotron_twotower_train_t192"]
# the table of ISSUE 39: metric -> (unit, source, layer, moves, cells)
ENTRIES = {
    "update_step_share": ("device_trace", "train step", "trained_steps_per_s", ALL_CELLS),
    "sample_step_share": ("device_trace", "device replay", "trained_steps_per_s",
                          ["geese_loop"]),
    "rollout_env_share": ("device_trace", "device rollout", "selfplay_steps_per_s",
                          ["geese_loop"]),
    "rollout_submit_share": ("program_counter", "learner loop (rollout thread)",
                             "selfplay_steps_per_s", ["geese_loop"]),
    "packed_padding_share": ("program_counter", "train step", "trained_steps_per_s",
                             ["nemotron_twotower_train_t192"]),
}
SCOPE_READERS = ("update_step_share", "sample_step_share", "rollout_env_share")

_program = layer_readers._program
TRAIN = "jit_%s(111)" % device_replay.TRAIN_PROGRAM
STREAM = "jit_%s(222)" % device_rollout.STREAM_PROGRAM

# a 2 s window of the loop: the fused train program 1.6 s in 5 runs (4 of them
# whole, 0.33 s each), the rollout 0.3 s in 3 whole runs.  By scope: the
# sampler 0.4 s (draw 0.1, rows 0.2, observations 0.06, 0.04 its own), the
# update 0.08 s; the rollout's reset 0.01, observe 0.05, policy 0.15, act
# 0.02, step 0.06 (0.01 under no scope)
REDUCED = {
    "window_s": 2.0,
    "programs": {TRAIN: _program(1.6, 5.0, 1.32, 4.0), STREAM: _program(0.3, 3.0)},
}
PHASES = {
    "sample": 0.4, "sample_draw": 0.1, "sample_rows": 0.2, "sample_obs": 0.06,
    "opt_update": 0.08, "env_reset": 0.01, "env_observe": 0.05,
    "rollout_policy": 0.15, "rollout_act": 0.02, "env_step": 0.06,
}
BY_HAND = {
    "update_step_share": 5.0,        # 0.08 / 1.6
    "sample_step_share": 25.0,       # 0.4 / 1.6: the parts lie inside it
    "rollout_env_share": 40.0,       # (0.01 + 0.05 + 0.06) / 0.3
}


def _read(run, name):
    return harness.load_module(run.path("layer_metrics", name + ".py")).read(run)


def _shared(run):
    return harness.load_module(run.path("layer_metrics", "program_phases.py"))


@pytest.fixture()
def run():
    """A traced run of the loop cell whose second pass has been made."""
    made = harness.Run(BENCH, "geese_loop", seed=1, seconds=30, trace=True,
                       rehearse=True, t_process=0.0)
    made.reduced = json.loads(json.dumps(REDUCED))
    made.xplane = "trace.xplane.pb"
    made.notes["phases"] = {k: {"seconds": v, "ops": 7.0} for k, v in PHASES.items()}
    made.t_window, made.window_s = 100.0, 2.0
    made.spans = [dict(s) for s in layer_readers.SPANS]
    return made


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------


def test_the_five_entries_are_appended_with_their_cells_and_a_reader_each():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    last = spec["per_layer"][-len(ENTRIES):]
    assert [m["name"] for m in last] == list(ENTRIES)
    layers = {m["layer"] for m in spec["per_layer"][:-len(ENTRIES)]}
    for metric in last:
        source, layer, moves, cells = ENTRIES[metric["name"]]
        assert metric == {"name": metric["name"], "unit": "%", "better": "lower",
                          "source": source, "layer": layer, "moves": moves,
                          "workloads": cells}
        assert layer in layers      # a layer the benchmark already names
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    # not a metric, and asked as one it answers none
    assert "program_phases" not in {m["name"] for m in spec["per_layer"]}


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_each_cell_is_handed_its_new_metrics(cell):
    made = harness.Run(BENCH, cell, seed=1, seconds=30, trace=True, rehearse=True,
                       t_process=0.0)
    names = set(made.metric_names("per_layer")) & set(ENTRIES)
    assert names == {name for name, entry in ENTRIES.items() if cell in entry[3]}


# ---------------------------------------------------------------------------
# by hand, on the reduced fixture
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_scope_reader_gives_the_share_worked_by_hand(run, name):
    assert _read(run, name) == pytest.approx(BY_HAND[name])


def test_each_scope_is_noted_in_milliseconds_a_whole_run(run):
    for name in SCOPE_READERS:
        _read(run, name)
    noted = run.notes["phases_ms_per_run"]
    assert set(noted) == set(PHASES)
    # the train program's whole runs take 330 ms, the rollout's 100
    assert noted["sample"] == pytest.approx(330.0 * 0.25)
    assert noted["sample_rows"] == pytest.approx(330.0 * 0.2 / 1.6)
    assert noted["opt_update"] == pytest.approx(330.0 * 0.05)
    assert noted["rollout_policy"] == pytest.approx(100.0 * 0.5)
    assert noted["env_step"] == pytest.approx(100.0 * 0.2)
    parts = sum(noted[k] for k in ("sample_draw", "sample_rows", "sample_obs"))
    assert parts <= noted["sample"]


def test_update_step_share_reads_the_cells_train_role():
    """A ``train_step`` cell names its program in its file."""
    made = harness.Run(BENCH, "xfmr_train_t64", seed=1, seconds=30, trace=True,
                       rehearse=True, t_process=0.0)
    made.reduced = {"window_s": 3.0, "programs": {"jit__step(9)": _program(3.0, 31.0)}}
    made.xplane = "trace.xplane.pb"
    made.notes["phases"] = {train_step.UPDATE_SCOPE: {"seconds": 0.27, "ops": 300.0}}
    assert _read(made, "update_step_share") == pytest.approx(9.0)


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_scope_reader_answers_none_with_nothing_to_read(run, name, monkeypatch):
    shared = _shared(run)
    # the program ran and no op carries the scope (the parent's program
    # under this PR's benchmark files: the pass found other scopes or none)
    kept = dict(run.notes["phases"])
    run.notes["phases"] = {}
    assert _read(run, name) is None
    run.notes["phases"] = {"attn0": {"seconds": 1.0, "ops": 3.0}}
    assert _read(run, name) is None
    # the scope is there and its program is not in the window
    run.notes["phases"] = kept
    programs, run.reduced["programs"] = run.reduced["programs"], {"jit_fn(7)": _program(1.0, 3.0)}
    assert _read(run, name) is None
    run.reduced["programs"] = programs
    assert _read(run, name) is not None
    # a program without the constants (an older commit) is asked for none
    for owner, constant in (shared.UPDATE, shared.SAMPLE, shared.SAMPLE_PARTS,
                            shared.ROLLOUT_ENV, shared.ROLLOUT):
        monkeypatch.delattr(owner, constant)
    assert _read(run, name) is None
    # an untraced run, or a rehearsal whose profile holds no device plane:
    # no second pass is made at all
    del run.notes["phases"]
    monkeypatch.undo()
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda *a, **k: pytest.fail("a pass"))
    run.reduced = None
    assert _read(run, name) is None
    run.reduced, run.xplane = json.loads(json.dumps(REDUCED)), None
    assert _read(run, name) is None
    assert "phases" not in run.notes and "phases_pass_s" not in run.notes


def test_the_helper_is_no_metric(run):
    assert _shared(run).read(run) is None


# ---------------------------------------------------------------------------
# the second pass, on a synthetic trace
# ---------------------------------------------------------------------------

REPLAY = "jit(replay_train)/while/body/closed_call/"
ROLLOUT = "jit(device_rollout)/while/body/closed_call/"


def _two_programs():
    """One chip, seconds.  Markers at 10 and 50.  ``jit_replay_train`` runs
    10-34: a gather under ``sample/sample_rows`` 10-14, the draw's prefix sum
    14-16, a mask under ``sample`` alone 16-17, the net forward 17-24 and
    backward 24-30, the update 30-33, a copy of the scan's under no scope
    33-34.  ``jit_device_rollout`` runs 34-50 and again 50-66, which the
    window leaves out: ``env_step`` 34-38, the policy 38-46, ``env_observe``
    46-48, unscoped 48-50.  An op before the window carries a scope too."""
    ops, op_names = [], []

    def op(name, start, end, op_name):
        ops.append(("%%%s = f32[8] fusion(f32[8] %%p)" % name, float(start), float(end)))
        op_names.append(op_name)

    op("gather.0", 4, 9, REPLAY + "sample/sample_rows/gather:")
    op("gather.1", 10, 14, REPLAY + "sample/sample_rows/gather:")
    op("fusion.2", 14, 16, REPLAY + "sample/sample_draw/cumsum:")
    op("fusion.3", 16, 17, REPLAY + "sample/jit(_where)/select_n:")
    op("fusion.4", 17, 24, REPLAY + "jvp(GeeseNet)/ConvBlock_0/conv_general_dilated:")
    op("fusion.5", 24, 30, REPLAY + "transpose(jvp(GeeseNet))/ConvBlock_0/conv_general_dilated:")
    op("fusion.6", 30, 33, REPLAY + "opt_update/add:")
    op("copy.7", 33, 34, "jit(replay_train)/while:")
    for start in (34, 50):
        op("fusion.8", start, start + 4, ROLLOUT + "env_step/select_n:")
        op("fusion.9", start + 4, start + 12, ROLLOUT + "rollout_policy/GeeseNet/conv_general_dilated:")
        op("copy.10", start + 12, start + 14, ROLLOUT + "env_observe/transpose:")
        op("fusion.11", start + 14, start + 16, ROLLOUT + "jit(_threefry_split)/slice:")
    return {
        "devices": {0: {
            "modules": [("jit_replay_train(1)", 4.0, 9.0), ("jit_replay_train(1)", 10.0, 34.0),
                        ("jit_device_rollout(2)", 34.0, 50.0),
                        ("jit_device_rollout(2)", 50.0, 66.0)],
            "ops": ops, "async_ops": [], "op_names": op_names}},
        "host": [(harness.WINDOW_BEGIN, 9.5, 10.0, "bench-watcher"),
                 ("rollout.dispatch", 12.0, 13.0, "device-rollout-1"),
                 (harness.WINDOW_END, 50.0, 50.5, "bench-watcher")],
    }


@pytest.fixture()
def passed(monkeypatch):
    """A run whose profile is ``_two_programs``: reduced as
    ``harness.reduce_profile`` reduces it, with ``load_xplane`` counting the
    passes the readers then make."""
    made = harness.Run(BENCH, "geese_loop", seed=1, seconds=30, trace=True,
                       rehearse=True, t_process=0.0)
    plain = _two_programs()
    for device in plain["devices"].values():
        del device["op_names"]
    plain["host"] = [s for s in plain["host"] if not s[0].startswith("bench.")]
    made.reduced = trace_reduce.reduce_trace(plain, (10.0, 50.0))
    made.xplane = "trace.xplane.pb"
    made.counters = {"fused_steps": 8}
    calls = []

    def load(path, scopes=None):
        calls.append((path, list(scopes)))
        return dict(_two_programs(), scopes=list(scopes))

    monkeypatch.setattr(trace_reduce, "load_xplane", load)
    return made, calls


def test_one_pass_serves_the_five_readers(passed):
    made, calls = passed
    values = {name: _read(made, name) for name in ENTRIES}
    values.update({name: _read(made, name) for name in ENTRIES})     # asked twice
    assert len(calls) == 1
    path, scopes = calls[0]
    assert path == made.xplane
    # every scope the program names, in one list
    assert set(scopes) >= set(PHASES) and len(scopes) == len(PHASES) + 1
    # replay_train 24 s: sample 4 + 2 + 1, update 3; rollout 16 s: env 4 + 2
    assert values["sample_step_share"] == pytest.approx(100.0 * 7 / 24)
    assert values["update_step_share"] == pytest.approx(100.0 * 3 / 24)
    assert values["rollout_env_share"] == pytest.approx(100.0 * 6 / 16)
    assert made.notes["phases_pass_s"] >= 0.0
    found = made.notes["phases"]
    # the window's markers cut the pass as they cut the first: the gather
    # before them and the rollout's second run are not in it
    assert found["sample_rows"] == {"seconds": pytest.approx(4.0), "ops": 1}
    assert found["env_step"] == {"seconds": pytest.approx(4.0), "ops": 1}
    assert found["rollout_policy"]["seconds"] == pytest.approx(8.0)
    assert "env_reset" not in found and "rollout_act" not in found   # no op carries them
    # forward | backward of the train program, from jax's own ``transpose(``
    assert made.notes["phases_backward"] == {
        "program_s": pytest.approx(24.0), "backward_s": pytest.approx(6.0),
        "backward_ops": 1}
    assert "backward_pass" not in found
    noted = made.notes["phases_ms_per_run"]
    assert noted["sample_draw"] == pytest.approx(2e3) and noted["env_observe"] == pytest.approx(2e3)


def test_the_scopes_of_one_program_stay_inside_it(passed):
    made, _ = passed
    shared = _shared(made)
    found = shared.phases(made)
    train = shared.train_program(made)
    parts = sum(found[k]["seconds"] for k in ("sample_draw", "sample_rows"))
    assert parts <= found["sample"]["seconds"]
    assert found["sample"]["seconds"] + found["opt_update"]["seconds"] <= train["seconds"]
    rollout = made.program_named("jit_" + device_rollout.STREAM_PROGRAM)
    covered = sum(found[k]["seconds"] for k in device_rollout.STREAM_SCOPES if k in found)
    assert covered == pytest.approx(rollout["seconds"] - 2.0)    # the key split


def test_the_pass_reads_the_kept_v5e_capture():
    """A real profile end to end: the capture predates the scopes (the pass
    finds none, and every scope reader answers None), and its backward pass
    is there under jax's own name."""
    found = glob.glob(os.path.join(
        REPO, "docs", "captures", "bf16_profile_2026-08-01_0854", "bf16", "plugins",
        "profile", "*", "*.xplane.pb"))
    if not found:
        pytest.skip("the kept bf16 trace is not in this checkout")
    made = harness.Run(BENCH, "xfmr_train_t64", seed=1, seconds=30, trace=True,
                       rehearse=True, t_process=0.0)
    made.cell = dict(made.cell, programs={"train": "jit__steps"})
    made.xplane = found[0]
    made.reduced = trace_reduce.reduce_trace(trace_reduce.load_xplane(found[0]))
    assert _read(made, "update_step_share") is None
    assert made.notes["phases"] == {} and made.notes["phases_pass_s"] > 0.0
    noted = made.notes["phases_backward"]
    assert 0.3 * noted["program_s"] < noted["backward_s"] < 0.9 * noted["program_s"]
    assert noted["program_s"] == pytest.approx(made.reduced["busy_s"])


# ---------------------------------------------------------------------------
# the two that read the program's own spans and counters
# ---------------------------------------------------------------------------


def test_rollout_submit_share_by_hand(run):
    # one hand-over of 0.2 s on the rollout thread, over 2 s
    assert _read(run, "rollout_submit_share") == pytest.approx(10.0)
    # the number ``rollout_wait_share`` keeps in its notes
    _read(run, "rollout_wait_share")
    assert run.notes["rollout_thread"]["rollout.submit_share"] == pytest.approx(10.0)
    # a span the window's end cuts counts by its part inside; another
    # thread's does not count
    run.spans.append(layer_readers._span("rollout.submit", 101.9, 0.3))
    run.spans.append(layer_readers._span("rollout.submit", 101.0, 0.5, thread="trainer"))
    assert _read(run, "rollout_submit_share") == pytest.approx(15.0)


def test_rollout_submit_share_answers_none_without_its_span(run):
    spans, run.xplane = run.spans, None
    # the traced window's profile never came back: test_profile_wait.py
    # names the readers that answer then, and this is none of them
    assert _read(run, "rollout_submit_share") is None
    run.xplane = "trace.xplane.pb"
    assert _read(run, "rollout_submit_share") == pytest.approx(10.0)
    run.spans = [s for s in spans if s["name"] != "rollout.submit"]
    assert _read(run, "rollout_submit_share") is None
    run.spans = []
    assert _read(run, "rollout_submit_share") is None


@pytest.mark.parametrize("counters, value", [
    ({"counter_packed_slots": 6656.0, "counter_observed_steps": 4992.0}, 25.0),
    ({"counter_packed_slots": 12288.0, "counter_observed_steps": 4915.2}, 60.0),
    ({"counter_packed_slots": 64.0, "counter_observed_steps": 64.0}, 0.0),
    ({"counter_packed_slots": 6656.0}, None),        # a net that counts no tokens
    ({"counter_observed_steps": 10.0}, None),
    ({"counter_packed_slots": 0.0, "counter_observed_steps": 0.0}, None),
    ({}, None),                                      # a net without the mixers
])
def test_packed_padding_share(run, counters, value):
    run.counters = counters
    got = _read(run, "packed_padding_share")
    assert got is None if value is None else got == pytest.approx(value)


# ---------------------------------------------------------------------------
# through the rehearsals (run.py on the CPU: no device plane)
# ---------------------------------------------------------------------------

root = rehearsal.root           # a benchmark root of tiny cells
hybrid_root = hybrid.root       # and one of the tiny routed cell


def test_rehearsed_loop_answers_rollout_submit_share_and_no_phase(root):
    """The program writes ``rollout.submit`` on the CPU as on the chip; the
    three scope readers are walked, find no reduced profile and make no
    second pass."""
    proc = rehearsal._run(root, "tiny_loop", 1)
    assert proc.returncode == 4, proc.stderr[-4000:]
    earlier = json.loads(proc.stdout.strip().splitlines()[-2])
    answered = set(earlier["notes"]["metrics_answered"])
    # both read the rollout thread's spans of the window: under six xdist
    # workers a 2 s window now and then holds none of them, and then neither
    # answers (test_layer_readers' own case asks for the first by name)
    assert ("rollout_submit_share" in answered) == ("rollout_wait_share" in answered)
    assert not answered & set(SCOPE_READERS)
    assert "phases" not in earlier["notes"] and "phases_pass_s" not in earlier["notes"]


def test_rehearsed_routed_cell_answers_packed_padding_share(hybrid_root):
    """``HybridNet`` counts its slots and tokens on the CPU too."""
    proc = hybrid._run(hybrid_root, "tiny_hybrid_train", 1)
    assert proc.returncode == 4, proc.stderr[-4000:]
    earlier = json.loads(proc.stdout.strip().splitlines()[-2])
    answered = set(earlier["notes"]["metrics_answered"])
    assert "packed_padding_share" in answered
    assert "update_step_share" not in answered
    counters = earlier["counters"]
    assert 0 < counters["counter_observed_steps"] <= counters["counter_packed_slots"]
