"""Test bootstrap: an 8-device virtual CPU platform, so multi-chip sharding
paths are exercised without TPU hardware.  Both variables are read when
jax is first imported, which is after this file.  The repo root goes on
``sys.path`` for the tests that import ``chip_smoke`` and ``benchmark``."""

import collections
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
    "benchmark.tests.balanced_router_cases",
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


# The driver runs the suite under ``-n 6 --dist loadfile`` inside a time limit.
# A file stays on one worker, and xdist hands files out by their number of
# cases, most first: a long file of few cases starts last and ends the run
# alone (PR 58 met it, PR 66's run was cut by it).  So the files that take a
# worker minutes go out first, longest first, whatever they count, and the
# others after them in xdist's order.  The order is by the seconds a worker
# spent on each under the driver's command on the builder's machine (PR 67:
# CHANGES.md has the table); a file that grows past two minutes joins the list.
_LONGEST_FIRST = (
    "test_benchmark_rehearsals.py",     # every case that runs benchmark/run.py, on one worker
    "test_parallel_grad_sync.py",
    "test_zaya_net.py",
    "test_benchmark_hybrid.py",
    "test_chip_compile_steps.py",
    "test_chip_compile.py",
    "test_trinity_net.py",
    "test_zaya_stack.py",
    "test_granite_net.py",
    "test_kanana_periods.py",
    "test_looped_net.py",
    "test_device_replay.py",
    "test_device_rollout.py",
    "test_chip_compile_cells.py",
    "test_kanana_net.py",
)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):      # xdist is there: keep the order made below
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    cases = collections.Counter(item.path.name for item in items)
    rank = {name: at for at, name in enumerate(_LONGEST_FIRST)}
    # stable: a file's cases stay together and in their order
    items.sort(key=lambda item: (rank.get(item.path.name, len(rank)), -cases[item.path.name]))
