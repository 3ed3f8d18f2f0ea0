"""benchmark/tests/test_ouro_reference.py and test_ouro_rehearsal.py,
collected where tests are run.  The rehearsal's cases run ``run.py`` on a
workload of their own (``benchmark_out/tiny_ouro_train``), so they share no
output directory with tests/test_benchmark_rehearsals.py or
tests/test_benchmark_hybrid.py."""

import pytest

from benchmark.tests.test_ouro_reference import *  # noqa: F401,F403
from benchmark.tests.test_ouro_rehearsal import *  # noqa: F401,F403  isort: skip
from test_setup_readers import SETUP_READERS  # isort: skip

# holds PR 41's entries to be the last of their lists, which PR 44's appended
# entries end: restated in tests/test_benchmark_granite.py
del test_the_entries_are_appended_and_nothing_else_moved  # noqa: F821


# holds the looped cell's traced run to an exact set of per-layer metrics,
# which PR 55's four ``setup_*`` metrics (every cell lists them) end: restated
del test_only_the_looped_cell_is_handed_the_new_metrics  # noqa: F821


@pytest.mark.parametrize("cell", ["geese_loop", "xfmr_train_t64", "xfmr_train_t64_dp4",
                                  "nemotron_twotower_train_t192", CELL])  # noqa: F405
def test_only_the_looped_cell_is_handed_the_new_metrics(cell):  # noqa: F811
    made = harness.Run(BENCH, cell, seed=1, seconds=30, trace=True, rehearse=True,  # noqa: F405
                       t_process=0.0)
    names = set(made.metric_names("per_layer"))
    assert (set(NEW_READERS) <= names) == (cell == CELL)  # noqa: F405
    assert not (set(NEW_READERS) & names) or cell == CELL  # noqa: F405
    assert set(SETUP_READERS) <= names
    if cell == CELL:    # noqa: F405  what its traced run must answer
        assert names == set(NEW_READERS) | set(SETUP_READERS) | {  # noqa: F405
            "setup_compile_s", "train_step_device_ms", "train_mfu", "train_roofline_share",
            "device_idle_share"}
        assert set(made.metric_names("end_to_end")) == {"trained_steps_per_s", "setup_s"}
