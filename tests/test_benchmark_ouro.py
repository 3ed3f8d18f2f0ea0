"""benchmark/tests/test_ouro_reference.py and test_ouro_rehearsal.py,
collected where tests are run.  The rehearsal's cases run ``run.py`` on a
workload of their own (``benchmark_out/tiny_ouro_train``), so they share no
output directory with tests/test_benchmark_rehearsals.py or
tests/test_benchmark_hybrid.py."""

from benchmark.tests.test_ouro_reference import *  # noqa: F401,F403
from benchmark.tests.test_ouro_rehearsal import *  # noqa: F401,F403  isort: skip

# holds PR 41's entries to be the last of their lists, which PR 44's appended
# entries end: restated in tests/test_benchmark_granite.py
del test_the_entries_are_appended_and_nothing_else_moved  # noqa: F821
