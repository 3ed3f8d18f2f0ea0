"""benchmark/tests/balanced_router_cases.py (``traffic.balance_routers``),
collected where tests are run, in a file of its own: under ``--dist
loadfile`` its cases can go to another worker than
tests/test_benchmark_reference.py's, which drops them after its own import."""

from benchmark.tests.balanced_router_cases import *  # noqa: F401,F403
