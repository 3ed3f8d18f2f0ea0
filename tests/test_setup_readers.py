"""The four ``setup_*`` readers (``benchmark/layer_metrics/setup_*.py``, PR 55)
on a hand-made run, their notes, their silence on a program without compile
records, and where their entries stand in ``BENCHMARK.json``.

The made-up set-up, on the monotonic clock (process start 100, window 110 to
114, so ``setup_s`` is 10):

====================  ========  =======  ===========  ======================
record                thread    phase    interval     says
====================  ========  =======  ===========  ======================
``_step``             Main      trace    101.0-103.0
``inner``             Main      trace    101.5-102.0  nested (inside _step)
folded remainder      Main      trace    101.0+0.3    nested, ``folded`` 40
``jit(_step)``        Main      lower    103.0-103.5
``jit(_step)``        Main      backend  103.5-105.5  hit, saved 30 s
``judge``             compiler  trace    102.5-103.2
``jit(judge)``        compiler  lower    103.2-103.4
``jit(judge)``        compiler  backend  103.4-106.0  miss
``jit(tiny)``         Main      backend  109.5-110.5  hit (the window cuts it)
``jit(after)``        Main      backend  115.0-116.0  miss (after the window)
====================  ========  =======  ===========  ======================

Phases: ``setup.import`` 100.2-100.9, ``setup.first_step`` 100.95-105.6 (it
waits for ``_step``'s trace, lowering and load), ``setup.init_state``
107.0-108.0, and, not a set-up phase, ``other.phase`` 108.0-109.0.

* every record, each second once: [101.0, 106.0] and [109.5, 110.0]: 5.5 s
  (the same records' seconds added up, as a sum over threads has them:
  2 + .5 + 2 + .7 + .2 + 2.6 + 1 = 9.0; with the nested and the folded ones,
  as ``compile_s`` adds them, 9.8)
* trace and lower: Main [101.0, 103.5], compiler [102.5, 103.4]: 2.5 s
* hits: [103.5, 105.5] and [109.5, 110.0]: 2.5 s
* named by a record or a phase: [100.2, 100.9], [100.95, 106.0], [107, 108],
  [109.5, 110.0]: 0.7 + 5.05 + 1.0 + 0.5 = 7.25 s; unspanned 10 - 7.25 = 2.75 s
"""

import json
import os

import pytest

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
SETUP_READERS = ("setup_compile_wall_s", "setup_trace_lower_s", "setup_cache_load_s",
                 "setup_unspanned_s")


# PR 58's configuration, its cell and its two metrics: the last of their lists now
PR58 = ("trinity_mini", "trinity_mini_train_t192", ("attn_proj_roofline", "qk_gate_step_share"))


def before_pr58(spec):
    """``spec`` as it stood before PR 58's entries, which are held to stand
    last: its configuration, its cell, its two metrics (each lists its cell
    alone), and its cell's name at the end of every older list it joined."""
    config, cell, metrics = PR58
    assert spec["configs"].pop()["name"] == config
    assert spec["workloads"].pop()["name"] == cell
    for name in reversed(metrics):
        last = spec["per_layer"].pop()
        assert (last["name"], last["workloads"]) == (name, [cell])
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if cell in metric.get("workloads", ()):
            assert metric["workloads"].pop() == cell
    return spec


def before_pr55(spec):
    """``spec`` as it stood before PR 55's four entries, which are held to
    stand last in ``per_layer``, each with every cell in the order ``workloads``
    lists them, under the layer and the end-to-end metric ``setup_compile_s``
    has.  The position checks of the PRs before (tests/test_benchmark_kanana.py,
    test_benchmark_zaya.py, test_benchmark_granite.py) ask of what is left what
    they asked before these four came; PR 58's entries, which follow them, are
    taken off first (``before_pr58``)."""
    spec = before_pr58(spec)
    cells = [w["name"] for w in spec["workloads"]]
    mine = spec["per_layer"][-len(SETUP_READERS):]
    assert [m["name"] for m in mine] == list(SETUP_READERS)
    older = next(m for m in spec["per_layer"] if m["name"] == "setup_compile_s")
    for metric in mine:
        assert metric == {
            "name": metric["name"], "unit": "s", "better": "lower", "source": "program_counter",
            "layer": older["layer"], "moves": older["moves"], "workloads": cells}
    assert (older["layer"], older["moves"]) == ("entry and compile cache", "setup_s")
    del spec["per_layer"][-len(SETUP_READERS):]
    return spec


def _record(program, phase, thread, t0, t1, **more):
    record = {"program": program, "phase": phase, "t_mono": t0, "dur_s": t1 - t0,
              "thread": thread, "nested": False}
    if phase == "backend":
        record.update(cache=None, retrieval_s=None, saved_s=None)
    return dict(record, **more)


RECORDS = [
    _record("inner", "trace", "MainThread", 101.5, 102.0, nested=True),
    _record("_step", "trace", "MainThread", 101.0, 103.0),
    _record("jit(_step)", "lower", "MainThread", 103.0, 103.5),
    _record("jit(_step)", "backend", "MainThread", 103.5, 105.5, cache="hit",
            retrieval_s=1.9, saved_s=30.0),
    _record("judge", "trace", "compiler", 102.5, 103.2),
    _record("jit(judge)", "lower", "compiler", 103.2, 103.4),
    _record("jit(judge)", "backend", "compiler", 103.4, 106.0, cache="miss"),
    _record("jit(tiny)", "backend", "MainThread", 109.5, 110.5, cache="hit",
            retrieval_s=0.9, saved_s=0.5),
    _record("jit(after)", "backend", "MainThread", 115.0, 116.0, cache="miss"),
    _record("<nested traces under 1 ms>", "trace", "MainThread", 101.0, 101.3, nested=True,
            folded=40),
]


def _phase(name, t0, t1, thread="MainThread"):
    return {"name": name, "ts": t0 + 1e9, "t_mono": t0, "dur_s": t1 - t0, "thread": thread,
            "rank": 0, "phase": True}


PHASES = [_phase("setup.import", 100.2, 100.9), _phase("setup.first_step", 100.95, 105.6),
          _phase("setup.init_state", 107.0, 108.0), _phase("other.phase", 108.0, 109.0)]


class _Counters:
    dropped = 2

    def __init__(self, records):
        self._records = records

    def programs(self):
        return [dict(r) for r in self._records]


class _OlderCounters:
    """The parent's: three totals and no record."""

    def snapshot(self):
        return {"hits": 1, "misses": 1, "compile_s": 9.8}


class _Fake:
    """What a ``setup_*`` reader asks of a ``Run``."""

    def __init__(self, compile, spans=(), t_window=110.0):
        self.compile, self.spans, self.notes = compile, list(spans), {}
        self.t_process, self.t_window, self.window_s = 100.0, t_window, 4.0
        self.values = {"setup_s": 10.0}

    def path(self, *parts):
        return os.path.join(BENCH, *parts)


def _read(name, run):
    return harness.load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read(run)


@pytest.fixture()
def no_process_phases(monkeypatch):
    """The readers also ask the program's tracer for its phases: none of this
    process's own (its import, the contexts other tests built) in the way."""
    from handyrl_tpu.utils import trace

    monkeypatch.setattr(trace._TRACER, "phases", [])


@pytest.mark.parametrize("name, expected", [
    ("setup_compile_wall_s", 5.5), ("setup_trace_lower_s", 2.5), ("setup_cache_load_s", 2.5),
    ("setup_unspanned_s", 2.75)])
def test_a_reader_reads_the_number_worked_out_by_hand(name, expected, no_process_phases):
    run = _Fake(_Counters(RECORDS), spans=PHASES)
    assert _read(name, run) == pytest.approx(expected, abs=1e-9)
    # at most the set-up, at most the thread-sum
    assert _read("setup_compile_wall_s", run) <= min(run.values["setup_s"], 9.0)


@pytest.mark.parametrize("source", ["spans", "tracer", "both"])
def test_the_phases_come_from_the_run_and_from_the_tracer_each_once(source, monkeypatch):
    """The ``train_step`` runner's ``run.spans`` is its whole ``trace.jsonl``,
    the two loop runners' only their window's: the tracer's own list has the
    phases either way, and a phase in both counts once."""
    from handyrl_tpu.utils import trace

    monkeypatch.setattr(trace._TRACER, "phases", PHASES if source != "spans" else [])
    run = _Fake(_Counters(RECORDS), spans=PHASES if source != "tracer" else [])
    assert _read("setup_unspanned_s", run) == pytest.approx(2.75, abs=1e-9)
    assert [p["phase"] for p in run.notes["setup_phases"]] == [
        "setup.import", "setup.first_step", "setup.init_state"]


@pytest.mark.parametrize("first", SETUP_READERS)
def test_whichever_reader_runs_first_writes_the_tables(first, no_process_phases):
    run = _Fake(_Counters(RECORDS), spans=PHASES)
    _read(first, run)
    notes = run.notes
    step, judge, tiny = notes["setup_programs"]
    assert step == {"program": "jit(_step)", "thread": "MainThread", "trace_s": 2.0,
                    "lower_s": 0.5, "backend_s": 2.0, "cache": "hit", "saved_s": 30.0}
    assert (judge["program"], judge["thread"], judge["cache"]) == ("jit(judge)", "compiler", "miss")
    assert (judge["trace_s"], judge["lower_s"], judge["backend_s"]) == pytest.approx((0.7, 0.2, 2.6))
    assert (tiny["program"], tiny["backend_s"], tiny["saved_s"]) == ("jit(tiny)", 1.0, 0.5)
    # the nested trace is its outer record's seconds, the folded ones too: no row
    assert notes["setup_misses"] == [
        {"program": "jit(judge)", "s": pytest.approx(2.6), "thread": "compiler"}]
    assert notes["setup_phases"] == [
        {"phase": "setup.import", "s": pytest.approx(0.7), "thread": "MainThread"},
        {"phase": "setup.first_step", "s": pytest.approx(4.65), "thread": "MainThread"},
        {"phase": "setup.init_state", "s": pytest.approx(1.0), "thread": "MainThread"}]
    after, = notes["after_window_programs"]
    assert (after["program"], after["backend_s"], after["cache"]) == ("jit(after)", 1.0, "miss")
    assert notes["compile_records_dropped"] == 2
    # the second reader leaves them as they are
    notes["setup_programs"] = "kept"
    _read(SETUP_READERS[(SETUP_READERS.index(first) + 1) % 4], run)
    assert run.notes["setup_programs"] == "kept"


def test_only_the_eight_largest_programs_are_listed(no_process_phases):
    many = [_record(f"jit(p{i})", "backend", "MainThread", 101.0 + i / 4, 101.0 + i / 4 + i / 100,
                    cache="miss") for i in range(1, 13)]
    run = _Fake(_Counters(many))
    _read("setup_compile_wall_s", run)
    assert [row["program"] for row in run.notes["setup_programs"]] == [
        f"jit(p{i})" for i in range(12, 4, -1)]
    assert len(run.notes["setup_misses"]) == 12       # every miss, though


@pytest.mark.parametrize("name", SETUP_READERS)
@pytest.mark.parametrize("why", ["no_programs", "no_window", "no_counters"])
def test_a_reader_answers_none_without_records_or_a_window(name, why, no_process_phases):
    """The parent's program under these files (``CompileCounters`` without
    ``programs()``), a run whose window never opened, a run without counters:
    the reader leaves its metric out, writes no note and does not raise."""
    run = {"no_programs": _Fake(_OlderCounters(), spans=PHASES),
           "no_window": _Fake(_Counters(RECORDS), spans=PHASES, t_window=None),
           "no_counters": _Fake(None, spans=PHASES)}[why]
    assert _read(name, run) is None
    assert run.notes == {}


def test_the_cache_reader_answers_none_where_the_cache_is_off(no_process_phases):
    """A CPU rehearsal: every ``backend`` record's ``cache`` is None.  The other
    three answer; all misses (a cold run) is an answer too: 0 s of loads."""
    off = [dict(r, cache=None) if r["phase"] == "backend" else r for r in RECORDS]
    assert _read("setup_cache_load_s", _Fake(_Counters(off))) is None
    assert _read("setup_compile_wall_s", _Fake(_Counters(off))) == pytest.approx(5.5)
    cold = [dict(r, cache="miss") if r["phase"] == "backend" else r for r in RECORDS]
    assert _read("setup_cache_load_s", _Fake(_Counters(cold))) == 0.0


def test_the_real_counters_feed_the_readers(no_process_phases):
    """``CompileCounters`` itself, one compile: the wall is the program's three
    records end to end, under the totals' sum."""
    import time

    import jax
    import jax.numpy as jnp

    from handyrl_tpu.utils.compile_cache import CompileCounters

    ones = jnp.ones((3,), jnp.float32)      # its own programs before the counters listen
    counters = CompileCounters()
    try:
        t_process = time.monotonic()

        def fed_body(x):
            for _ in range(50):
                x = jnp.sin(x) * 1.25
            return x
        jax.jit(fed_body)(jnp.ones((3,), jnp.float32))
    finally:
        counters.close()
    run = _Fake(counters, t_window=time.monotonic())
    run.t_process = t_process
    wall = _read("setup_compile_wall_s", run)
    assert 0 < _read("setup_trace_lower_s", run) < wall <= counters.snapshot()["compile_s"]
    assert wall <= run.t_window - run.t_process
    assert _read("setup_unspanned_s", run) == pytest.approx(run.t_window - t_process - wall)
    assert run.notes["setup_programs"][0]["program"] == "jit(fed_body)"


def test_the_four_entries_stand_last_and_every_cell_is_handed_them():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = [w["name"] for w in spec["workloads"]]
    assert len(cells) == 9 and cells[-1] == PR58[1]     # PR 58's is handed them too
    before_pr55(spec)
    assert not {m["name"] for m in spec["per_layer"]} & set(SETUP_READERS)
    for name in SETUP_READERS:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for cell in cells:
        made = harness.Run(BENCH, cell, seed=1, seconds=30, trace=True, rehearse=True,
                           t_process=0.0)
        assert set(SETUP_READERS) <= set(made.metric_names("per_layer"))
        assert "setup_s" in made.metric_names("end_to_end")
