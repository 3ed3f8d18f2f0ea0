"""benchmark/tests/test_hybrid_reference.py and test_hybrid_rehearsal.py,
collected where tests are run.  The rehearsal's cases run ``run.py`` on a
workload of their own (``benchmark_out/tiny_hybrid_train``), so they share
no output directory with tests/test_benchmark_rehearsals.py; the routed
cell's cases of test_phase_readers.py and test_expert_buffer_fill.py run it
too, so they are collected here."""

from benchmark.tests.test_hybrid_reference import *  # noqa: F401,F403
from benchmark.tests.test_hybrid_rehearsal import *  # noqa: F401,F403  isort: skip

# holds the routed cell to be the last of ``setup_compile_s``'s cells, which the
# cell PR 44 appended ends: restated in tests/test_benchmark_granite.py
del test_every_new_metric_lists_the_cell_and_has_a_reader  # noqa: F821
from benchmark.tests.test_phase_readers import (  # noqa: F401  isort: skip
    hybrid_root, test_rehearsed_routed_cell_answers_packed_padding_share,
)
from benchmark.tests.test_expert_buffer_fill import (  # noqa: F401  isort: skip
    test_rehearsed_routed_cell_answers_expert_buffer_fill,
)
