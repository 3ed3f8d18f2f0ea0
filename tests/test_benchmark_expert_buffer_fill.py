"""benchmark/tests/test_expert_buffer_fill.py, but for the case that runs
``benchmark/run.py``: it is collected in test_benchmark_hybrid.py, with the
other runs of the tiny routed cell (they share an output directory)."""

from benchmark.tests.test_expert_buffer_fill import *  # noqa: F401,F403

del test_rehearsed_routed_cell_answers_expert_buffer_fill  # noqa: F821
