"""benchmark/tests/test_phase_readers.py, but for the two cases that run
``benchmark/run.py``: the loop's is in test_benchmark_rehearsals.py (which
says why), the routed cell's in test_benchmark_hybrid.py."""

from benchmark.tests.test_phase_readers import *  # noqa: F401,F403

del test_rehearsed_loop_answers_rollout_submit_share_and_no_phase  # noqa: F821
del test_rehearsed_routed_cell_answers_packed_padding_share  # noqa: F821
