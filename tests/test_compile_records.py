"""``CompileCounters``' record per program and phase (docs/observability.md,
"Set-up: compile records and phases"): what a record holds, which clock it is
on, what ``nested`` marks, the totals the benchmark's harness reads
(``snapshot()``: the three keys and the values a duration listener gives),
the persistent cache's verdict on a ``backend`` record, the list's bound, the
``compile.*`` events a tracer takes, and the line a slow build leaves on
stderr."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import trace_reduce
from handyrl_tpu.utils import trace as trace_mod
from handyrl_tpu.utils.compile_cache import CompileCounters
from handyrl_tpu.utils.trace import read_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_EVENTS = ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                  "backend_compile_duration")
# benchmark/harness.py FALLBACK_MARKERS: a line of the program's with one of
# these fails a run's ``no_fallback_marker``
FALLBACK_MARKERS = ("falling back", "degrading", "device generation stops",
                    "giving up on the rollout thread", "starting fresh", "Traceback")


@pytest.fixture()
def counters():
    made = CompileCounters()
    yield made
    made.close()


def _nested_pair(scale):
    """A fresh ``outer`` that calls a fresh jitted ``inner`` (jax caches a
    trace by the function's identity: a second test would find nothing to
    compile in a shared pair)."""
    @jax.jit
    def inner(x):
        for _ in range(40):          # a body long enough to trace for over 1 ms
            x = jnp.sin(x) * scale
        return x

    @jax.jit
    def outer(x):
        return inner(x) + 1.0

    return outer


def _union_s(records):
    return trace_reduce.measure(trace_reduce.merge(
        [(r["t_mono"], r["t_mono"] + r["dur_s"]) for r in records]))


@pytest.fixture()
def nested_run(counters):
    """The records and totals of one nested compile, beside what a duration
    listener of the test's own summed over the same events."""
    seen = []

    def listen(name, duration, **kwargs):
        if name.rsplit("/", 1)[-1] in COMPILE_EVENTS:
            seen.append(float(duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    before = time.monotonic()
    try:
        _nested_pair(1.0625)(jnp.ones((3,), jnp.float32))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    after = time.monotonic()
    return {"records": counters.programs(), "snapshot": counters.snapshot(),
            "listened": seen, "bracket": (before, after)}


@pytest.mark.parametrize("phase, program", [
    ("trace", "outer"), ("lower", "jit(outer)"), ("backend", "jit(outer)")])
def test_the_outer_program_has_one_record_a_phase(nested_run, phase, program):
    mine = [r for r in nested_run["records"] if r["program"] == program and r["phase"] == phase]
    assert len(mine) == 1
    record = mine[0]
    assert record["nested"] is False and record["thread"] == "MainThread"
    assert record["dur_s"] > 0
    # on the spans' clock: inside the bracket the test took on it
    before, after = nested_run["bracket"]
    assert before - 1e-3 <= record["t_mono"] <= record["t_mono"] + record["dur_s"] <= after + 1e-3
    if phase == "backend":      # no persistent cache in this process: no verdict
        assert (record["cache"], record["retrieval_s"], record["saved_s"]) == (None, None, None)
    else:
        assert "cache" not in record


def test_the_inner_trace_is_kept_and_marked_nested(nested_run):
    records = nested_run["records"]
    inner, = [r for r in records if r["program"] == "inner"]
    outer, = [r for r in records if r["program"] == "outer"]
    assert inner["phase"] == "trace" and inner["nested"] is True
    assert outer["t_mono"] <= inner["t_mono"]
    assert inner["t_mono"] + inner["dur_s"] <= outer["t_mono"] + outer["dur_s"] + 1e-6
    # inlined, not compiled: the inner program has no lowering of its own
    assert not [r for r in records if r["program"] == "jit(inner)"]
    # the ``jnp`` one-liners inside the traced body cost one record a thread
    folded, = [r for r in records if r.get("folded")]
    assert folded["program"] == CompileCounters.FOLDED and folded["nested"] is True
    assert folded["folded"] >= 1 and 0 < folded["dur_s"] < folded["folded"] * 1e-3


def test_snapshot_keeps_its_three_keys_and_a_duration_listeners_values(nested_run):
    snapshot = nested_run["snapshot"]
    assert sorted(snapshot) == ["compile_s", "hits", "misses"]
    assert (snapshot["hits"], snapshot["misses"]) == (0, 0)
    assert snapshot["compile_s"] == pytest.approx(sum(nested_run["listened"]), rel=1e-9)
    # every event's seconds are in the records too, the folded ones' with them
    assert sum(r["dur_s"] for r in nested_run["records"]) == pytest.approx(
        snapshot["compile_s"], rel=1e-9)


def test_the_union_counts_a_nested_second_once(nested_run):
    records = [r for r in nested_run["records"] if not r.get("folded")]
    total = nested_run["snapshot"]["compile_s"]
    inner, = [r for r in records if r["program"] == "inner"]
    assert _union_s(records) <= total - inner["dur_s"] + 1e-6
    assert _union_s(records) == pytest.approx(
        _union_s([r for r in records if not r["nested"]]), abs=1e-9)


def test_two_threads_compiling_at_once_give_a_wall_under_the_thread_sum(counters):
    gate = threading.Barrier(2)

    def compile_one(scale):
        def body(x):
            for _ in range(300):
                x = jnp.cos(x) * scale + 1.0
            return x
        gate.wait()
        jax.jit(body)(jnp.ones((5,), jnp.float32))

    threads = [threading.Thread(target=compile_one, args=(1.0 + i / 8,), name=f"compiler-{i}")
               for i in range(2)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    records = [r for r in counters.programs() if not r["nested"]]
    assert {r["thread"] for r in records} == {"compiler-0", "compiler-1"}
    by_thread = {name: sum(r["dur_s"] for r in records if r["thread"] == name)
                 for name in ("compiler-0", "compiler-1")}
    assert min(by_thread.values()) > 0.05
    wall, thread_sum = _union_s(records), sum(by_thread.values())
    assert wall < 0.9 * thread_sum
    assert wall <= counters.snapshot()["compile_s"]


def test_the_lists_bound_drops_and_counts(counters):
    counters.MAX_RECORDS = 3    # the pair leaves four: two traces, a lowering, a build
    _nested_pair(1.125)(jnp.ones((3,), jnp.float32))
    kept = [r for r in counters.programs() if not r.get("folded")]
    assert len(kept) == 3 and counters.dropped >= 1
    # a dropped record's seconds stay in the totals
    assert counters.snapshot()["compile_s"] > sum(r["dur_s"] for r in counters.programs())


def test_a_tracer_takes_the_compile_events_at_their_own_start(counters, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    assert trace_mod.configure({"enabled": True, "path": path, "flush_interval": 0.05})
    try:
        def body(x):
            for _ in range(300):     # 10 ms of tracing and more
                x = jnp.tanh(x) * 1.03125
            return x
        jax.jit(body)(jnp.ones((7,), jnp.float32))
    finally:
        trace_mod.shutdown()
    events = {}
    for record in read_trace(path):
        if record["name"].startswith("compile."):
            events.setdefault(record["name"], []).append(record)
    assert {"compile.trace", "compile.build"} <= set(events)
    assert "compile.load" not in events         # no cache, no hit
    build = next(e for e in events["compile.build"] if e["attrs"]["program"] == "jit(body)")
    mine, = [r for r in counters.programs()
             if r["program"] == "jit(body)" and r["phase"] == "backend"]
    assert build["t_mono"] == pytest.approx(mine["t_mono"], abs=1e-5)
    assert build["dur_s"] == pytest.approx(mine["dur_s"], abs=1e-6)
    assert build["thread"] == "MainThread" and build["attrs"]["cache"] is None
    traced = next(e for e in events["compile.trace"] if e["attrs"]["program"] == "body")
    assert traced["dur_s"] >= CompileCounters.EVENT_MIN_S
    # a nested or a short record is in ``programs()`` and not in the file
    assert all(e["dur_s"] >= CompileCounters.EVENT_MIN_S
               for name in ("compile.trace", "compile.lower") for e in events.get(name, []))


_CHILD = """
import json, sys
from handyrl_tpu.utils.compile_cache import CompileCounters, enable_compile_cache
import jax, jax.numpy as jnp
assert enable_compile_cache() == sys.argv[1]
counters = CompileCounters(report_build_s=float(sys.argv[2]))
def cached_body(x):
    return jnp.sin(x) @ jnp.cos(x).T
jax.jit(cached_body)(jnp.ones((16, 16), jnp.float32)).block_until_ready()
print(json.dumps({"programs": counters.programs(), "snapshot": counters.snapshot()}))
"""


@pytest.fixture(scope="module")
def cold_then_warm(tmp_path_factory):
    """One child process run twice on a compile cache of its own: (records,
    snapshot, stderr) of the cold run and of the warm one.  A build is said on
    stderr from 0 s on."""
    cache = str(tmp_path_factory.mktemp("compile_cache"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    runs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _CHILD, cache, "0"], env=env, cwd=REPO,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-4000:]
        said = json.loads(proc.stdout.strip().splitlines()[-1])
        runs.append((said["programs"], said["snapshot"], proc.stderr))
    return runs


def _backend(programs, program="jit(cached_body)"):
    record, = [r for r in programs if r["program"] == program and r["phase"] == "backend"]
    return record


@pytest.mark.parametrize("turn, verdict", [(0, "miss"), (1, "hit")])
def test_a_backend_record_says_what_the_cache_said(cold_then_warm, turn, verdict):
    programs, snapshot, _ = cold_then_warm[turn]
    record = _backend(programs)
    assert record["cache"] == verdict
    verdicts = [r["cache"] for r in programs if r["phase"] == "backend"]
    assert snapshot["hits"] == verdicts.count("hit")
    assert snapshot["misses"] == verdicts.count("miss")
    if verdict == "hit":
        # reading took time, inside the record; the entry says what it saved
        assert 0 < record["retrieval_s"] <= record["dur_s"]
        assert isinstance(record["saved_s"], float)
    else:
        assert record["retrieval_s"] is None and record["saved_s"] is None
    assert all("cache" not in r for r in programs if r["phase"] != "backend")


def test_a_slow_build_is_said_once_by_name_and_a_load_is_not(cold_then_warm):
    (_, _, cold), (_, _, warm) = cold_then_warm
    lines = [line for line in cold.splitlines()
             if line.startswith("[handyrl_tpu] built jit(cached_body) in ")]
    assert len(lines) == 1
    assert lines[0].endswith(" s (no entry in the compile cache)")
    assert not [marker for marker in FALLBACK_MARKERS if marker in lines[0]]
    assert "built jit(cached_body)" not in warm


def test_a_build_under_the_threshold_is_not_said(counters, capsys):
    assert counters.report_build_s == 5.0
    _nested_pair(1.1875)(jnp.ones((3,), jnp.float32))
    assert "[handyrl_tpu] built" not in capsys.readouterr().err


def test_closed_counters_record_nothing_more(counters):
    _nested_pair(1.25)(jnp.ones((3,), jnp.float32))
    counters.close()
    held, total = len(counters.programs()), counters.snapshot()["compile_s"]
    _nested_pair(1.3125)(jnp.ones((3,), jnp.float32))
    assert len(counters.programs()) == held
    assert counters.snapshot()["compile_s"] == total
