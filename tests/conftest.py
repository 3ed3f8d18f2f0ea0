"""Test bootstrap: an 8-device virtual CPU platform, so multi-chip sharding
paths are exercised without TPU hardware.  Both variables are read when
jax is first imported, which is after this file.  The repo root goes on
``sys.path`` for the tests that import ``chip_smoke`` and ``benchmark``."""

import itertools
import os
import socket
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

# tests/test_benchmark_*.py re-export the cases of benchmark/tests/ (the
# instrument every PR is judged by; nothing else collects them): their
# asserts are rewritten like any test module's
pytest.register_assert_rewrite(
    "benchmark.tests.test_layer_readers", "benchmark.tests.test_phase_readers",
    "benchmark.tests.test_profile_wait",
    "benchmark.tests.test_reference", "benchmark.tests.test_rehearsal",
    "benchmark.tests.test_trace_reduce",
)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The kernel hands ports from 32768 up to outgoing connections and to
# bind-to-0, so a port found by bind-to-0-then-close can be taken again
# (by another xdist worker's client socket, say) before the server under
# test binds it.  Ports below that range are taken only by who names them.
_PORT_FLOOR, _PORT_SPAN = 20000, 1500
_worker = int((os.environ.get("PYTEST_XDIST_WORKER") or "gw0")[2:]) % 8
_ports = itertools.cycle(
    range(_PORT_FLOOR + _worker * _PORT_SPAN, _PORT_FLOOR + (_worker + 1) * _PORT_SPAN)
)


def free_port() -> int:
    """A port for a server that takes a port NUMBER (where the server can
    bind port 0 itself, read the port back instead): the next of this xdist
    worker's own range that binds now, the way the servers bind
    (SO_REUSEADDR: a port in TIME_WAIT from the last session is free)."""
    for port in itertools.islice(_ports, _PORT_SPAN):
        with socket.socket() as probe:
            probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                probe.bind(("", port))
            except OSError:
                continue
        return port
    raise RuntimeError("no free port in this worker's range")
