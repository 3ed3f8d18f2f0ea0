"""benchmark/tests/test_hybrid_reference.py and test_hybrid_rehearsal.py,
collected where tests are run.  The rehearsal's cases run ``run.py`` on a
workload of their own (``benchmark_out/tiny_hybrid_train``), so they share
no output directory with tests/test_benchmark_rehearsals.py."""

from benchmark.tests.test_hybrid_reference import *  # noqa: F401,F403
from benchmark.tests.test_hybrid_rehearsal import *  # noqa: F401,F403  isort: skip
