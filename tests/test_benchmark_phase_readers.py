"""benchmark/tests/test_phase_readers.py, but for the two cases that run
``benchmark/run.py``: the loop's is in test_benchmark_rehearsals.py (which
says why), the routed cell's in test_benchmark_hybrid.py.

One case is restated here: PR 39's file holds its five entries to be the
*last* five of ``per_layer``, which the next metric appended after them (PR
40's ``expert_buffer_fill``) ends, and a PR that claims a gain may not edit
a file the benchmark has (PERF.md section 7 leaves the edit to a
``benchmark`` PR).  The case below asks what that one meant: the five are
there, in their order, one after another, after everything older."""

import json
import os

from benchmark.tests import test_phase_readers as _phase_readers
from benchmark.tests.test_phase_readers import *  # noqa: F401,F403

del test_rehearsed_loop_answers_rollout_submit_share_and_no_phase  # noqa: F821
del test_rehearsed_routed_cell_answers_packed_padding_share  # noqa: F821


def test_the_five_entries_are_appended_with_their_cells_and_a_reader_each():
    entries, bench, repo = _phase_readers.ENTRIES, _phase_readers.BENCH, _phase_readers.REPO
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index(next(iter(entries)))
    assert names[first:first + len(entries)] == list(entries)
    assert first == 18      # after every metric the benchmark had before them
    layers = {m["layer"] for m in spec["per_layer"][:first]}
    for metric in spec["per_layer"][first:first + len(entries)]:
        source, layer, moves, cells = entries[metric["name"]]
        # cells appended since (PR 44's acting cell to ``rollout_env_share``) come after
        listed = metric.pop("workloads")
        assert listed[:len(cells)] == cells
        assert metric == {"name": metric["name"], "unit": "%", "better": "lower",
                          "source": source, "layer": layer, "moves": moves}
        assert layer in layers      # a layer the benchmark already names
        assert os.path.exists(os.path.join(bench, "layer_metrics", metric["name"] + ".py"))
    # not a metric, and asked as one it answers none
    assert "program_phases" not in names
