"""benchmark/tests/test_trace_reduce.py, collected where tests are run."""

from benchmark.tests.test_trace_reduce import *  # noqa: F401,F403
