"""benchmark/tests/test_layer_readers.py, but for the one case that runs
``benchmark/run.py`` (test_benchmark_rehearsals.py has it, and says why)."""

from benchmark.tests.test_layer_readers import *  # noqa: F401,F403

del test_rehearsed_loop_answers_rollout_wait_share  # noqa: F821
