"""The reducer on the two v5e traces the repo keeps (real planes, real
nesting) and on synthetic traces whose answers are known by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_trace_reduce.py -q
"""

import glob
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402

CAPTURES = os.path.join(REPO, "docs", "captures", "bf16_profile_2026-08-01_0854")


def _capture(kind):
    found = glob.glob(os.path.join(CAPTURES, kind, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        pytest.skip(f"the kept {kind} trace is not in this checkout")
    return tr.load_xplane(found[0])


@pytest.mark.parametrize("kind", ["bf16", "fp32"])
def test_kept_v5e_trace_adds_up(kind):
    trace = _capture(kind)
    assert list(trace["devices"]) == [0]
    modules = trace["devices"][0]["modules"]
    assert any(name.startswith("jit__steps(") for name, _, _ in modules)
    # the window from the first program's start to the last op's end
    lo = min(s for _, s, _ in modules)
    hi = max(e for _, _, e in trace["devices"][0]["ops"])
    reduced = tr.reduce_trace(trace, (lo, hi))
    assert reduced["busy_s"] + reduced["idle_s"] == pytest.approx(reduced["window_s"], abs=1e-12)
    assert sum(p["seconds"] for p in reduced["programs"].values()) == pytest.approx(
        reduced["busy_s"], abs=1e-9)
    # one fused k-step program does nearly all of it
    steps = next(v for k, v in reduced["programs"].items() if k.startswith("jit__steps("))
    assert steps["runs"] == 1 and steps["seconds"] > 0.98 * reduced["busy_s"]
    # self times of the nested op line sum to the busy time too
    assert sum(o["seconds"] for o in reduced["ops"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)
    assert reduced["collective_s"] == 0.0
    assert len(tr.breakdown(reduced)["device_ops"]) == 10


def test_hand_checked_gap_in_the_bf16_trace():
    """Read off the trace by hand (peek at the planes): the one-op
    ``jit_convert_element_type`` program starts at 4,981,753 ns, the first
    op of ``jit__steps`` at 5,242,883 ns.  The tiny program has no event on
    the op line, so from the window's start to that op the chip is idle:
    261,130 ns, with no host span open."""
    trace = _capture("bf16")
    lo = 4981753e-9
    hi = max(e for _, _, e in trace["devices"][0]["ops"])
    reduced = tr.reduce_trace(trace, (lo, hi))
    name, seconds, offset = reduced["longest_gaps"][0]
    assert name == tr.NO_SPAN and offset == pytest.approx(0.0, abs=1e-12)
    assert seconds == pytest.approx(261130e-9, abs=2e-9)
    assert reduced["idle_s"] == pytest.approx(261130e-9, rel=1e-3)


def _synthetic():
    """One chip, microseconds written as seconds for legibility:
    0-10 compute, 10-12 a synchronous all-reduce (exposed: nothing else can
    run), 12-20 nothing (the host is inside epoch.metrics_fetch, which
    itself sits inside a wider train_epoch span), 20-30 an asynchronous
    all-reduce with compute under it at 20-26 and its -done at 29-30."""
    ops = [
        ("%fusion.1 = f32[8] fusion(f32[8] %p)", 0.0, 10.0),
        ("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", 10.0, 12.0),
        ("%all-reduce-start.2 = f32[8] all-reduce-start(f32[8] %y)", 20.0, 20.5),
        ("%fusion.2 = f32[8] fusion(f32[8] %q)", 20.5, 26.0),
        ("%all-reduce-done.2 = f32[8] all-reduce-done(f32[8] %z)", 29.0, 30.0),
    ]
    return {
        "devices": {0: {
            "modules": [("jit_step(1)", 0.0, 12.0), ("jit_step(1)", 20.0, 30.0)],
            "ops": ops,
            "async_ops": [("%all-reduce-start.2 = f32[8] all-reduce-start(f32[8] %y)", 20.0, 30.0)],
        }},
        "host": [("train_epoch", 0.0, 30.0, "main"),
                 ("epoch.metrics_fetch", 12.0, 20.0, "trainer")],
    }


def test_synthetic_exposed_and_hidden_collective():
    reduced = tr.reduce_trace(_synthetic(), (0.0, 30.0))
    # busy: 0-12 and the ops of 20-30 (20-26, 29-30); 26-29 only the async
    # interval is open, which is no op running
    assert reduced["busy_s"] == pytest.approx(12.0 + 6.0 + 1.0)
    assert reduced["idle_s"] == pytest.approx(30.0 - 19.0)
    assert reduced["collective_s"] == pytest.approx(2.0 + 10.0)
    # the sync one whole; of the async one what fusion.2 does not cover
    assert reduced["collective_exposed_s"] == pytest.approx(2.0 + (10.0 - 5.5))
    # no run is cut by this window: the whole-run pair equals the clipped one
    assert reduced["programs"]["jit_step(1)"] == {
        "seconds": pytest.approx(19.0), "runs": 2,
        "whole_seconds": pytest.approx(19.0), "whole_runs": 2}
    gaps = {name: v["seconds"] for name, v in reduced["idle_by_span"].items()}
    # innermost span at the gap's middle: the fetch for 12-20, the epoch
    # span for 26-29
    assert gaps == {"epoch.metrics_fetch": pytest.approx(8.0), "train_epoch": pytest.approx(3.0)}
    assert reduced["longest_gaps"][0][:2] == ("epoch.metrics_fetch", pytest.approx(8.0))


def _back_to_back(n, length=10.0, gap=1.0):
    """``n`` runs of one program, each ``length`` busy, ``gap`` apart."""
    starts = [i * (length + gap) for i in range(n)]
    return {"devices": {0: {
        "modules": [("jit_replay_train(9)", s, s + length) for s in starts],
        "ops": [("%fusion.1 = f32[8] fusion(f32[8] %p)", s, s + length) for s in starts],
        "async_ops": [],
    }}, "host": []}


@pytest.mark.parametrize("window, clipped, whole", [
    # the window opens 4 into the first run and closes 7 into the fifth
    ((4.0, 51.0), (6.0 + 30.0 + 7.0, 5), (30.0, 3)),
    # cuts only the last run
    ((0.0, 36.0), (30.0 + 3.0, 4), (30.0, 3)),
    # on the runs' own edges, and in the gaps around them: none cut
    ((0.0, 54.0), (50.0, 5), (50.0, 5)),
    ((10.5, 43.5), (30.0, 3), (30.0, 3)),
    # shorter than one run: a share of the window, no time per run
    ((13.0, 18.0), (5.0, 1), (0.0, 0)),
])
def test_runs_the_window_cuts_are_told_from_whole_ones(window, clipped, whole):
    """A time per run divides whole runs only; a share of the window keeps
    the clipped seconds (section 7 of PERF.md before PR 28: 20.77 ms read
    for programs of 22.6 ms an update, a cut run counted as one)."""
    program = tr.reduce_trace(_back_to_back(5), window)["programs"]["jit_replay_train(9)"]
    assert (program["seconds"], program["runs"]) == (pytest.approx(clipped[0]), clipped[1])
    assert (program["whole_seconds"], program["whole_runs"]) == (
        pytest.approx(whole[0]), whole[1])
    if whole[1]:
        assert program["whole_seconds"] / program["whole_runs"] == pytest.approx(10.0)


def test_ops_outside_any_program_have_no_whole_run():
    trace = _back_to_back(2)
    trace["devices"][0]["ops"].append(("%copy.1 = f32[8] copy(f32[8] %p)", 30.0, 31.0))
    programs = tr.reduce_trace(trace, (0.0, 40.0))["programs"]
    assert programs[tr.NO_PROGRAM] == {
        "seconds": pytest.approx(1.0), "runs": 0, "whole_seconds": 0.0, "whole_runs": 0}


def test_plane_sizes_of_a_kept_trace_add_up():
    """The wire walk against what ProfileData parses from the same file."""
    found = glob.glob(os.path.join(CAPTURES, "bf16", "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        pytest.skip("the kept bf16 trace is not in this checkout")
    with open(found[0], "rb") as f:
        raw = f.read()
    sizes = tr.plane_sizes(raw)
    trace = tr.load_xplane(found[0])
    device = sizes["/device:TPU:0"]
    assert device["lines"]["XLA Ops"]["events"] == len(trace["devices"][0]["ops"])
    assert device["lines"]["Async XLA Ops"]["events"] == len(trace["devices"][0]["async_ops"])
    assert device["lines"]["XLA Modules"]["events"] == len(trace["devices"][0]["modules"])
    assert sum(v["bytes"] for v in device["lines"].values()) < device["bytes"]
    # the planes are all of the file but a few bytes of framing a plane
    assert 0 <= len(raw) - sum(p["bytes"] for p in sizes.values()) < 16 * len(sizes)


def test_two_chips_are_averaged():
    trace = _synthetic()
    trace["devices"][1] = {"modules": [("jit_step(1)", 0.0, 6.0)],
                           "ops": [("%fusion.1 = f32[8] fusion()", 0.0, 6.0)], "async_ops": []}
    reduced = tr.reduce_trace(trace, (0.0, 30.0))
    assert reduced["chips"] == 2
    assert reduced["busy_s"] == pytest.approx((19.0 + 6.0) / 2)
    assert reduced["per_device_busy_s"] == [pytest.approx(19.0), pytest.approx(6.0)]


def test_self_times_of_nested_ops():
    ops = [("%while.1 = () while()", 0.0, 10.0), ("%a = f32[] add()", 1.0, 3.0),
           ("%call.1 = () call()", 4.0, 9.0), ("%b = f32[] add()", 5.0, 6.0)]
    order, self_s, leaf = tr.self_times(ops)
    by_name = {ops[i][0].split(" ")[0]: (s, l) for i, s, l in zip(order, self_s, leaf)}
    assert by_name["%while.1"] == (pytest.approx(3.0), False)
    assert by_name["%call.1"] == (pytest.approx(4.0), False)
    assert by_name["%a"] == (pytest.approx(2.0), True)
    assert by_name["%b"] == (pytest.approx(1.0), True)


def test_interval_arithmetic_against_brute_force():
    rng = np.random.default_rng(0)
    grid = np.arange(0, 1000)                       # unit cells [k, k+1)
    for _ in range(20):
        starts = rng.integers(0, 990, 40)
        iv = np.stack([starts, starts + rng.integers(1, 10, 40)], 1).astype(float)
        covered = np.zeros(1000, bool)
        for s, e in iv.astype(int):
            covered[s:e] = True
        merged = tr.merge(iv)
        assert (merged[1:, 0] > merged[:-1, 1]).all()
        assert tr.measure(merged) == covered.sum()
        assert tr.measure(tr.complement(merged, 100.0, 900.0)) == (~covered[100:900]).sum()
        windows = np.stack([rng.integers(0, 500, 5), rng.integers(500, 1000, 5)], 1).astype(float)
        want = [covered[int(a):int(b)].sum() for a, b in windows]
        assert tr.measure_inside(merged, windows) == pytest.approx(want)
        other = tr.merge(np.stack([grid[::7], grid[::7] + 3], 1).astype(float))
        both = covered & np.isin(grid % 7, [0, 1, 2])
        assert tr.measure(tr.intersect(merged, other)) == both.sum()
        assert tr.measure(tr.subtract(merged, other)) == (covered & ~both).sum()


# -- device time by named scope -------------------------------------------------

STEP = "jit(_step)/jit(main)/"


def _scoped():
    """One chip, one 20 s program: attn1 forward 0-4 (a 1 s child op inside
    it carries the scope too), attn1 backward 4-7 under jax's transform
    wrappers, attn10 (no component of which is attn1) 7-12, a fusion with
    no op_name 12-14, mlp_up0 14-20 of which the window keeps a part."""
    ops = [
        ("%fusion.1 = f32[8] fusion(f32[8] %p)", 0.0, 4.0),
        ("%add.1 = f32[8] add(f32[8] %p)", 1.0, 2.0),
        ("%fusion.2 = f32[8] fusion(f32[8] %p)", 4.0, 7.0),
        ("%fusion.3 = f32[8] fusion(f32[8] %p)", 7.0, 12.0),
        ("%fusion.4 = f32[8] fusion(f32[8] %p)", 12.0, 14.0),
        ("%fusion.5 = f32[8] fusion(f32[8] %p)", 14.0, 20.0),
    ]
    op_names = [
        STEP + "jvp(TransformerNet)/attn1/q/dot_general:",
        STEP + "jvp(TransformerNet)/attn1/q/add:",
        STEP + "transpose(jvp(TransformerNet))/attn1/q/dot_general:",
        STEP + "jvp(TransformerNet)/attn10/q/dot_general:",
        "",
        STEP + "transpose(jvp(mlp_up0))/dot_general:",
    ]
    return {"devices": {0: {"modules": [("jit__step(1)", 0.0, 20.0)], "ops": ops,
                            "async_ops": [], "op_names": op_names}},
            "host": [], "scopes": ["attn1", "mlp_up0", "TransformerNet", "enc1"]}


def test_scopes_count_forward_and_backward_and_whole_components_only():
    reduced = tr.reduce_trace(_scoped(), (0.0, 16.0))
    scopes = reduced["scopes"]
    # self seconds: the forward fusion 4 - 1 of its child, the child 1, the backward 3
    assert scopes["attn1"] == {"seconds": pytest.approx(7.0), "ops": 3}
    # a wrapped component counts, and an op the window's edge cuts counts
    # whole, as in the op table
    assert scopes["mlp_up0"] == {"seconds": pytest.approx(6.0), "ops": 1}
    assert scopes["TransformerNet"] == {"seconds": pytest.approx(12.0), "ops": 4}
    assert "enc1" not in scopes                 # no op carries it
    assert sum(scopes[name]["seconds"] for name in ("attn1", "mlp_up0")) <= sum(
        op["seconds"] for op in reduced["ops"].values())
    # nothing else of the reduction moves
    plain = _scoped()
    del plain["scopes"]
    unscoped = tr.reduce_trace(plain, (0.0, 16.0))
    assert "scopes" not in unscoped
    for key in ("busy_s", "idle_s", "programs", "ops", "collective_s"):
        assert unscoped[key] == reduced[key]


def test_scopes_are_averaged_over_chips():
    trace = _scoped()
    trace["devices"][1] = {
        "modules": [], "async_ops": [], "ops": [("%fusion.1 = f32[8] fusion()", 0.0, 3.0)],
        "op_names": [STEP + "jvp(TransformerNet)/attn1/q/dot_general:"]}
    scopes = tr.reduce_trace(trace, (0.0, 20.0))["scopes"]
    assert scopes["attn1"] == {"seconds": pytest.approx((7.0 + 3.0) / 2), "ops": 2.0}


@pytest.mark.parametrize("op_name, found", [
    ("jit(_step)/jit(main)/jvp(TransformerNet)/attn1/q/dot_general:", ["attn1"]),
    ("jit(_step)/jit(main)/transpose(jvp(attn1))/q/transpose:", ["attn1"]),
    ("jit(_step)/jit(main)/jvp(TransformerNet)/attn10/attn1_q/dot_general:", []),
    ("jit(_step)/jit(main)/attn1/enc1/add:add", ["attn1", "enc1"]),
    ("attn1", ["attn1"]),
    ("", []),
])
def test_a_scope_is_a_whole_path_component(op_name, found):
    assert tr.scopes_of(op_name, ["attn1", "enc1"]) == found


def test_op_names_are_read_off_the_kept_v5e_trace():
    """The stat is ``tf_op`` on the op's event metadata (looked at by hand
    in the bf16 capture): GeeseNet's twelfth block, forward and backward."""
    found = glob.glob(os.path.join(CAPTURES, "bf16", "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        pytest.skip("the kept bf16 trace is not in this checkout")
    plain = tr.load_xplane(found[0])
    assert "scopes" not in plain and "op_names" not in plain["devices"][0]
    trace = tr.load_xplane(found[0], scopes=["ConvBlock_12", "GeeseNet", "NoSuchScope"])
    device = trace["devices"][0]
    assert len(device["op_names"]) == len(device["ops"]) == len(plain["devices"][0]["ops"])
    paths = set(device["op_names"])
    assert any("/jvp(GeeseNet)/ConvBlock_12/" in p for p in paths)
    assert any("/transpose(jvp(GeeseNet))/ConvBlock_12/" in p for p in paths)
    reduced = tr.reduce_trace(trace)
    scopes = reduced["scopes"]
    assert set(scopes) == {"ConvBlock_12", "GeeseNet"}
    assert 0 < scopes["ConvBlock_12"]["seconds"] < scopes["GeeseNet"]["seconds"] < reduced["busy_s"]
    assert tr.reduce_trace(plain)["busy_s"] == reduced["busy_s"]
