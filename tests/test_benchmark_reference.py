"""benchmark/tests/test_reference.py, collected where tests are run."""

from benchmark.tests.test_reference import *  # noqa: F401,F403
