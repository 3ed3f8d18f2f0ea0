"""benchmark/tests/test_reference.py, collected where tests are run."""

from benchmark.tests import balanced_router_cases
from benchmark.tests.test_reference import *  # noqa: F401,F403

# test_reference.py's last line hands ``balanced_router_cases``' names on:
# dropped here, they are collected once, by tests/test_benchmark_balanced_router.py
for _name in balanced_router_cases.__all__:
    del globals()[_name]
