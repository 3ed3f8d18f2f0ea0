"""The reader of the routed experts' row-buffer fill (``expert_buffer_fill``):
its entry in BENCHMARK.json, the share worked out by hand from the step's
counters, nothing where a program has no such counter (the parent of the PR
that brought it), and through the tiny routed rehearsal, where ``HybridNet``
counts its buffers on the CPU too.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_expert_buffer_fill.py -q
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

import test_hybrid_rehearsal as hybrid  # noqa: E402
from benchmark import harness  # noqa: E402

NAME = "expert_buffer_fill"
CELL = "nemotron_twotower_train_t192"
hybrid_root = hybrid.root       # the tiny routed cell's benchmark root


def _run(cell=CELL):
    return harness.Run(BENCH, cell, seed=1, seconds=30, trace=True, rehearse=True,
                       t_process=0.0)


def _read(run):
    return harness.load_module(run.path("layer_metrics", NAME + ".py")).read(run)


def test_the_entry_lists_the_routed_cell_alone_and_has_its_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "trained_steps_per_s", "workloads": [CELL]}]
    # a layer the benchmark already names, and an end-to-end metric the cell reports
    others = [m for m in spec["per_layer"] if m["name"] != NAME]
    assert "kernels" in {m["layer"] for m in others}
    moved = next(m for m in spec["end_to_end"] if m["name"] == "trained_steps_per_s")
    assert CELL in moved["workloads"]
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


@pytest.mark.parametrize("cell", ["geese_loop", "xfmr_train_t64", "xfmr_train_t64_dp4", CELL])
def test_only_the_routed_cell_is_handed_the_metric(cell):
    assert (NAME in _run(cell).metric_names("per_layer")) == (cell == CELL)


def test_the_share_by_hand_and_the_passes_in_the_notes():
    run = _run()
    # four routed layers x two window parts: 8,320 slots a layer, 2,080 rows
    run.counters = {"counter_rows_held": 8320.0, "counter_buffer_slots": 33280.0,
                    "counter_expert_passes": 0.25}
    assert _read(run) == pytest.approx(25.0)
    assert run.notes["expert_buffer"] == {
        "rows_held": 8320.0, "buffer_slots": 33280.0, "expert_passes": 0.25}
    # a buffer no row fell into is 0% full, not unanswered
    run.counters["counter_rows_held"] = 0.0
    assert _read(run) == 0.0


@pytest.mark.parametrize("counters", [
    {},                                                             # not a routed net
    {"counter_rows_held": 8192.0, "counter_expert_rows_max": 1900.0},  # the parent's program
    {"counter_buffer_slots": 33280.0},
    {"counter_rows_held": 8192.0, "counter_buffer_slots": 0.0},
])
def test_nothing_to_read_is_no_answer_and_no_note(counters):
    run = _run()
    run.counters = dict(counters)
    assert _read(run) is None
    assert run.notes == {}


def test_rehearsed_routed_cell_answers_expert_buffer_fill(hybrid_root):
    """The tiny routed cell's runner is the published cell's: what the
    entry lists for the one is handed to the other; readers answer a traced run."""
    proc = hybrid._run(hybrid_root, "tiny_hybrid_train", 1)
    assert proc.returncode == 4, proc.stderr[-4000:]
    earlier = json.loads(proc.stdout.strip().splitlines()[-2])
    assert NAME in earlier["notes"]["metrics_answered"]
    counters, noted = earlier["counters"], earlier["notes"]["expert_buffer"]
    assert 0 < counters["counter_rows_held"] <= counters["counter_buffer_slots"]
    assert counters["counter_buffer_slots"] % 128 == 0
    assert counters["counter_expert_passes"] >= 0
    assert noted["buffer_slots"] == counters["counter_buffer_slots"]
