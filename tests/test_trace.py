"""Observability plane: the span tracer, its exporter, and the
zero-overhead pin (docs/observability.md).

The contract under test, in priority order:

1. **Provably free when off** — `trace_span` disabled returns ONE shared
   no-op object (no allocation), and a real `batch_pipeline: device`
   window with tracing off records ZERO blocking host syncs and ZERO XLA
   recompiles under the PR 9 sanitizers: the instrumentation cannot have
   added a hot-path cost it claims not to have.
2. **Never blocking when on** — a full span ring DROPS and counts
   (`trace_dropped`), the flusher drains in the background, and a
   trace-enabled window still shows zero recompiles (spans are host-side
   bookkeeping, not device work).
3. **Crash-tolerant** — `read_trace` tolerates exactly one truncated
   FINAL line; mid-file corruption raises.
4. **Exportable** — the Chrome/Perfetto exporter's mapping is pinned by
   a committed golden (regenerate intentionally with
   HANDYRL_REGEN_GOLDEN=1).
"""

import json
import os
import sys
import threading
import time
from pathlib import Path

import pytest

pytestmark = pytest.mark.obs

from handyrl_tpu.utils import trace as trace_mod
from handyrl_tpu.utils.trace import (
    META_NAME,
    read_trace,
    trace_event,
    trace_span,
    trace_stats,
)

GOLDEN_DIR = Path(__file__).parent / "golden"
SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")


@pytest.fixture(autouse=True)
def _tracer_reset():
    """Every test starts from a tracer disarmed and counted from zero, and
    leaves it disarmed (the module singleton is process-global state shared
    with any Learner or TrainContext the suite builds: a one-off event that
    another file of this xdist worker recorded stays in ``trace_stats``
    until the next ``configure``).  The phase list is the process's too (its
    import, every context another file built, up to the list's bound, and
    ``configure`` writes them into a new sink): emptied here, put back after."""
    trace_mod.configure(None)
    tracer = trace_mod._TRACER
    kept, dropped = tracer.phases, tracer.phases_dropped
    tracer.phases, tracer.phases_dropped = [], 0
    yield
    trace_mod.shutdown()
    tracer.phases, tracer.phases_dropped = kept, dropped


def _configure(tmp_path, rank=0, **over):
    cfg = {"enabled": True, "path": str(tmp_path / "trace.jsonl"),
           "ring_size": 4096, "flush_interval": 0.05}
    cfg.update(over)
    assert trace_mod.configure(cfg, rank=rank)
    return trace_mod.current_path()


# -- disabled path ------------------------------------------------------------


def test_disabled_span_is_one_shared_noop_object():
    """The disabled fast path allocates nothing: every call returns the
    SAME context-manager instance, and nothing is recorded."""
    a = trace_span("x", plane="learner")
    b = trace_span("y")
    assert a is b
    with a:
        pass
    trace_event("z", 0.5)
    assert trace_stats() == {"trace_spans": 0, "trace_dropped": 0}


def test_unwritable_sink_fails_at_configure_naming_the_knob(tmp_path):
    """A run ASKED to trace must fail at startup, not record nothing."""
    with pytest.raises(ValueError, match="trace.path"):
        trace_mod.configure({
            "enabled": True,
            "path": str(tmp_path / "no" / "such" / "dir" / "t.jsonl"),
        })
    assert not trace_mod.enabled()


# -- enabled recording --------------------------------------------------------


def test_span_nesting_and_attribution(tmp_path):
    path = _configure(tmp_path)
    with trace_span("outer", plane="learner"):
        with trace_span("inner", step=3):
            time.sleep(0.01)

    done = threading.Event()

    def worker():
        with trace_span("threaded"):
            pass
        done.set()

    threading.Thread(target=worker, name="obs-worker", daemon=True).start()
    assert done.wait(5.0)
    trace_mod.shutdown()

    recs = {r["name"]: r for r in read_trace(path) if r["name"] != META_NAME}
    assert set(recs) == {"outer", "inner", "threaded"}
    outer, inner = recs["outer"], recs["inner"]
    # temporal containment: the nested span lies inside its parent
    assert outer["t_mono"] <= inner["t_mono"]
    assert inner["t_mono"] + inner["dur_s"] <= outer["t_mono"] + outer["dur_s"] + 1e-6
    assert inner["dur_s"] >= 0.01
    assert inner["attrs"] == {"step": 3}
    assert outer["attrs"] == {"plane": "learner"}
    # attribution: thread name + rank ride every record
    assert recs["threaded"]["thread"] == "obs-worker"
    assert all(r["rank"] == 0 for r in recs.values())
    # the wall<->monotonic anchor is the file's first line
    first = read_trace(path)[0]
    assert first["name"] == META_NAME and first["version"] >= 1


def test_ring_overflow_drops_counted_never_blocking(tmp_path):
    _configure(tmp_path, ring_size=8, flush_interval=999.0)  # flusher idle
    t0 = time.perf_counter()
    for _ in range(100):
        trace_event("spam", 0.001)
    elapsed = time.perf_counter() - t0
    stats = trace_stats()
    assert stats["trace_spans"] == 8
    assert stats["trace_dropped"] == 92
    # 100 drops in well under a flush interval: the full ring never blocks
    assert elapsed < 1.0


def test_rank_suffix_path_derivation(tmp_path):
    path = _configure(tmp_path, rank=2)
    assert path.endswith("trace.rank2.jsonl")
    with trace_span("s"):
        pass
    trace_mod.shutdown()
    recs = read_trace(path)
    assert all(r["rank"] == 2 for r in recs)


# -- crash tolerance ----------------------------------------------------------


def test_truncated_tail_tolerated_mid_file_raises(tmp_path):
    path = _configure(tmp_path)
    for i in range(3):
        trace_event(f"s{i}", 0.001)
    trace_mod.shutdown()
    # a kill mid-append leaves a half-written FINAL line: tolerated
    with open(path, "a") as f:
        f.write('{"name": "torn", "ts": 1.0, "dur_')
    recs = read_trace(path)
    assert [r["name"] for r in recs if r["name"] != META_NAME] == ["s0", "s1", "s2"]
    # but corruption anywhere EARLIER is a real integrity failure
    lines = open(path).read().splitlines()
    lines[1] = lines[1][: len(lines[1]) // 2]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(ValueError):
        read_trace(path)


# -- Perfetto export ----------------------------------------------------------


def _export_chrome():
    sys.path.insert(0, SCRIPTS)
    try:
        from trace_export import export_chrome
    finally:
        sys.path.remove(SCRIPTS)
    return export_chrome


def test_perfetto_export_matches_golden():
    """The exporter's mapping (event shape, cross-rank wall alignment,
    deterministic tid assignment) is pinned by a committed golden built
    from the fixture files; regenerate with HANDYRL_REGEN_GOLDEN=1."""
    export_chrome = _export_chrome()
    record_lists = [
        read_trace(str(GOLDEN_DIR / "trace_fixture.jsonl")),
        read_trace(str(GOLDEN_DIR / "trace_fixture_rank1.jsonl")),
    ]
    out = export_chrome(record_lists)
    golden_path = GOLDEN_DIR / "trace_perfetto.json"
    if os.environ.get("HANDYRL_REGEN_GOLDEN"):
        golden_path.write_text(json.dumps(out, indent=1) + "\n")
        pytest.skip("golden regenerated; commit tests/golden/ and re-run")
    assert out == json.loads(golden_path.read_text()), (
        "Perfetto export drifted from the committed golden; if intentional, "
        "regenerate with HANDYRL_REGEN_GOLDEN=1"
    )


def test_real_trace_round_trips_through_the_exporter(tmp_path):
    """write -> read_trace -> export: every recorded span becomes exactly
    one complete ('X') event with in-range timestamps."""
    path = _configure(tmp_path)
    with trace_span("a", plane="learner"):
        with trace_span("b"):
            pass
    trace_event("c", 0.01, plane="pipeline")
    trace_mod.shutdown()
    export_chrome = _export_chrome()
    recs = read_trace(path)
    out = export_chrome([recs])
    xs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["name"] for e in xs) == ["a", "b", "c"]
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    assert {e["cat"] for e in xs} == {"learner", "pipeline", "trace"}


def test_export_cli_writes_chrome_trace(tmp_path):
    import subprocess

    path = _configure(tmp_path)
    with trace_span("cli_span"):
        pass
    trace_mod.shutdown()
    out_path = tmp_path / "export.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "trace_export.py"), path,
         "-o", str(out_path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    data = json.loads(out_path.read_text())
    assert any(e["name"] == "cli_span" for e in data["traceEvents"])


def test_scope_split_sums_a_recorded_profile_by_scope_and_op(tmp_path):
    """scripts/scope_split.py on the recorded bf16 capture: every op's self
    time lands under the one scope given or under ``outside``, by op kind,
    in milliseconds a run; the rows fall by cost."""
    import subprocess

    capture = os.path.join(
        os.path.dirname(SCRIPTS), "docs", "captures", "bf16_profile_2026-08-01_0854", "bf16",
        "plugins", "profile", "2026_08_01_08_56_05", "vm.xplane.pb")
    out_path = tmp_path / "split.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, "scope_split.py"), capture, "closed_call",
         "-o", str(out_path)],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr
    assert "== closed_call: " in proc.stdout and "== outside: " in proc.stdout
    split = json.loads(out_path.read_text())
    inside, outside = (sum(ms for ms, _ in split["ms_per_run"][scope].values())
                       for scope in ("closed_call", "outside"))
    assert inside > outside > 0          # the scanned update's body holds most of the time
    rows = split["rows"]
    assert abs(sum(row[0] for row in rows) - inside - outside) < 1e-6
    assert [row[0] for row in rows] == sorted((row[0] for row in rows), reverse=True)
    assert {row[3] for row in rows} <= {"fwd", "bwd"}
    assert any(row[4].startswith("fusion:") for row in rows) and any(row[4] == "copy" for row in rows)


# -- the zero-overhead pin (acceptance) ---------------------------------------


def _pipeline_window():
    """One warm batch_pipeline: device window (the test_sanitizers
    surface): pipeline batch() sampling dispatches + real train steps."""
    # tests/ is on sys.path under pytest's rootdir insertion (no
    # tests/__init__.py), same mechanism the scripts use for _logparse
    from test_sanitizers import _device_pipeline

    return _device_pipeline(dp=2)


@pytest.mark.slow
def test_trace_disabled_window_is_sync_and_recompile_free():
    """Acceptance pin: with `trace: false` (the default) the instrumented
    hot path — dispatch_serialized spans, pipeline wait events, train-step
    spans all compiled IN but disarmed — adds ZERO blocking host syncs and
    ZERO XLA recompiles to a warm streaming window.  This is the harness
    that keeps 'off by default and provably free' true."""
    from handyrl_tpu.utils.sanitizers import HostSyncSanitizer, RecompileSentinel

    assert not trace_mod.enabled()
    pipe, ctx, state, stop = _pipeline_window()
    try:
        batch = pipe.batch()  # warm: ring init + sampler jit
        assert batch is not None
        state, _ = ctx.train_step(state, batch, 1e-5)
        with HostSyncSanitizer() as sync, RecompileSentinel() as sentinel:
            for _ in range(4):
                batch = pipe.batch()
                assert batch is not None
                state, _ = ctx.train_step(state, batch, 1e-5)
        sync.assert_clean("trace: false device-pipeline window")
        sentinel.assert_no_recompiles("trace: false device-pipeline window")
    finally:
        stop.set()
        pipe.stop()


@pytest.mark.slow
def test_trace_enabled_window_records_spans_without_recompiles(tmp_path):
    """Arming the tracer must not change the compiled program either: the
    same warm window records the dispatch/train/pipe spans and still
    shows zero XLA recompiles (spans are host bookkeeping, not device
    work)."""
    from handyrl_tpu.utils.sanitizers import RecompileSentinel

    pipe, ctx, state, stop = _pipeline_window()
    try:
        batch = pipe.batch()
        assert batch is not None
        state, _ = ctx.train_step(state, batch, 1e-5)
        path = _configure(tmp_path)
        with RecompileSentinel() as sentinel:
            for _ in range(4):
                batch = pipe.batch()
                assert batch is not None
                state, _ = ctx.train_step(state, batch, 1e-5)
        trace_mod.shutdown()
        sentinel.assert_no_recompiles("trace: true device-pipeline window")
        names = {r["name"] for r in read_trace(path)}
        # the window's seams all reported: the per-dispatch spans and the
        # pipeline's measured waits
        assert "dispatch.run" in names, names
        assert "dispatch.wait" in names, names
    finally:
        stop.set()
        pipe.stop()


# -- set-up phases (docs/observability.md "Set-up: compile records and phases") ---


@pytest.fixture()
def phase_list():
    """The tracer, whose phase list ``_tracer_reset`` emptied for the test."""
    return trace_mod._TRACER


def _phases_so_far():
    """Phases recorded, kept or dropped: what no loop may move."""
    tracer = trace_mod._TRACER
    return len(tracer.phases) + tracer.phases_dropped


def test_a_phase_is_recorded_with_no_tracer_and_costs_no_file(phase_list, tmp_path, monkeypatch):
    from handyrl_tpu.utils.trace import trace_phase, trace_phase_since

    monkeypatch.chdir(tmp_path)
    assert not trace_mod.enabled()
    before = time.monotonic()
    with trace_phase("setup.unit", plane="learner"):
        time.sleep(0.01)
    trace_phase_since("setup.since", before)
    first, second = trace_mod.phases()
    assert first["name"] == "setup.unit" and first["phase"] is True
    assert first["attrs"] == {"plane": "learner"} and first["thread"] == "MainThread"
    assert before <= first["t_mono"] and 0.01 <= first["dur_s"] < 5.0
    assert abs((first["ts"] - first["t_mono"]) - (time.time() - time.monotonic())) < 0.05
    assert second["name"] == "setup.since" and "attrs" not in second
    assert second["t_mono"] == pytest.approx(before, abs=1e-6)
    assert second["dur_s"] >= first["dur_s"]
    # a copy: the caller's edits are its own
    trace_mod.phases()[0]["name"] = "edited"
    assert trace_mod.phases()[0]["name"] == "setup.unit"
    assert os.listdir(tmp_path) == [] and trace_stats() == {"trace_spans": 0, "trace_dropped": 0}
    # and ``trace_span`` off is still the shared null span
    assert trace_span("a") is trace_mod._NULL_SPAN


def test_the_phase_list_is_bounded_and_counts_what_it_drops(phase_list):
    from handyrl_tpu.utils.trace import trace_phase

    phase_list.max_phases, was = 3, phase_list.max_phases
    try:
        for i in range(5):
            with trace_phase("setup.unit", i=i):
                pass
    finally:
        phase_list.max_phases = was
    assert [p["attrs"]["i"] for p in trace_mod.phases()] == [0, 1, 2]
    assert phase_list.phases_dropped == 2 and _phases_so_far() == 5


def test_configure_writes_earlier_phases_behind_the_meta_line(phase_list, tmp_path):
    from handyrl_tpu.utils.trace import trace_phase

    with trace_phase("setup.before", n=1):
        pass
    path = _configure(tmp_path)
    with trace_span("a_span"):
        pass
    with trace_phase("setup.after"):
        pass
    trace_mod.shutdown()
    records = read_trace(path)
    assert [r["name"] for r in records] == [META_NAME, "setup.before", "a_span", "setup.after"]
    before, span, after = records[1:]
    assert before["phase"] is True and before["attrs"] == {"n": 1}
    assert before["t_mono"] < records[0]["t_mono"]      # it ended before the sink opened
    assert "phase" not in span and after["phase"] is True
    # both are in the process's list, whichever way they reached the file
    assert [p["name"] for p in trace_mod.phases()] == ["setup.before", "setup.after"]
    # the one recorded while the tracer was on went through the ring
    assert trace_stats()["trace_spans"] == 2
    # a second sink gets every phase so far behind its meta line
    again = _configure(tmp_path / "..", path=str(tmp_path / "again.jsonl"))
    trace_mod.shutdown()
    assert [r["name"] for r in read_trace(again)] == [META_NAME, "setup.before", "setup.after"]


def test_the_export_shows_phases_and_compile_events_on_their_threads(phase_list, tmp_path):
    """A file that holds both: a phase recorded before the sink opened, a
    phase recorded after, and the ``compile.*`` events of a program compiled
    on a thread of its own, each on the thread that ran it."""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.utils.compile_cache import CompileCounters
    from handyrl_tpu.utils.trace import trace_phase

    with trace_phase("setup.before"):
        pass
    path = _configure(tmp_path)
    counters = CompileCounters()

    def compile_one():
        def exported_body(x):
            for _ in range(300):
                x = jnp.sin(x) * 1.015625
            return x
        with trace_phase("setup.on_a_thread"):
            jax.jit(exported_body)(jnp.ones((9,), jnp.float32))

    try:
        thread = threading.Thread(target=compile_one, name="compiler")
        thread.start()
        thread.join()
    finally:
        counters.close()
        trace_mod.shutdown()
    out = _export_chrome()([read_trace(path)])
    tid = {e["args"]["name"]: e["tid"] for e in out["traceEvents"] if e["name"] == "thread_name"}
    assert set(tid) == {"MainThread", "compiler"}
    xs = {e["name"]: e for e in out["traceEvents"] if e["ph"] == "X"
          and e["args"].get("program", "jit(exported_body)") == "jit(exported_body)"}
    assert xs["setup.before"]["tid"] == tid["MainThread"] and xs["setup.before"]["cat"] == "setup"
    assert xs["setup.on_a_thread"]["tid"] == tid["compiler"]
    assert xs["compile.build"]["tid"] == tid["compiler"] and xs["compile.build"]["cat"] == "compile"
    assert xs["compile.build"]["args"] == {"program": "jit(exported_body)", "cache": None}
    # the build lies inside the phase that waited for it
    phase, build = xs["setup.on_a_thread"], xs["compile.build"]
    assert phase["ts"] <= build["ts"] and build["ts"] + build["dur"] <= phase["ts"] + phase["dur"] + 1
    traced = [e for e in out["traceEvents"] if e["name"] == "compile.trace"]
    assert [e["args"]["program"] for e in traced] == ["exported_body"]


def test_twenty_train_steps_add_no_phase(phase_list):
    """The contract of ``trace_phase``: a context, its state and its step's
    first call are phases, once each; the updates behind them are not."""
    import jax

    from benchmark import traffic
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh

    cfg = normalize_args({
        "env_args": {"env": "TicTacToe", "net": "transformer",
                     "net_args": {"d_model": 32, "n_heads": 2, "n_layers": 1, "memory_len": 8}},
        "train_args": {"batch_size": 4, "forward_steps": 4, "burn_in_steps": 2,
                       "observation": True, "seq_attention": "einsum"}})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    env = make_env(args["env"])
    module = env.net()
    params = traffic.seeded_params(module, env, 5)
    host_batches = traffic.random_play_batches(env, module, args, 2, 16)
    ctx = TrainContext(module, args, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    state = ctx.init_state(params)
    batches = [ctx.put_batch(b) for b in host_batches]
    state, _ = ctx.train_step(state, batches[0], 1e-5)
    names = [p["name"] for p in trace_mod.phases()]
    assert names[-3:] == ["setup.train_context", "setup.init_state", "setup.first_step"]
    assert set(names[:-3]) <= {"setup.net"}     # the process's first net, if this built it
    first = trace_mod.phases()[-1]
    assert first["attrs"] == {"plane": "learner", "program": "_step"} and first["dur_s"] > 0.05
    so_far = _phases_so_far()
    for i in range(20):
        state, metrics = ctx.train_step(state, batches[i % 2], 1e-5)
    jax.block_until_ready(metrics)
    # one phase for each program the context bound (a second packed bound, if
    # the second batch brought one), none for an update
    assert _phases_so_far() - so_far == ctx._train_step._cache_size() - 1 <= 1


def test_five_dispatches_of_an_actor_loop_add_no_phase(phase_list):
    """``actor_loop`` against a loopback gateway: its weights and its first
    dispatch are phases; by the first record batch the gateway sees, every
    phase of the loop is recorded, and five more dispatches add none."""
    import socket

    import jax

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.runtime import actor_host
    from handyrl_tpu.runtime.plane import PlaneGateway

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    dist = {"role": "actor", "coordinator_address": f"127.0.0.1:{port}", "plane_port": port,
            "initialization_timeout": 30.0}
    cfg = normalize_args({"env_args": {"env": "HungryGeese"}, "train_args": {
        "turn_based_training": False, "observation": False, "device_rollout_games": 4,
        "device_replay_k_steps": 4, "seed": 7, "distributed": dist}})
    seen = []

    def on_records(records):
        seen.append(_phases_so_far())
        if len(seen) == 6:
            gateway.begin_stop()

    gateway = PlaneGateway(dist, on_records=on_records)
    gateway.start()
    try:
        done = actor_host.actor_loop(cfg, jax.devices()[:1], threading.Event())
    finally:
        gateway.stop()
    assert done["dispatches"] == 6 and len(seen) == 6
    assert seen == [seen[0]] * 6 and _phases_so_far() == seen[0]
    names = [p["name"] for p in trace_mod.phases()]
    assert names[-2:] == ["setup.actor_weights", "setup.actor_first_dispatch"]
    assert set(names[:-2]) <= {"setup.net"}
    weights, first = trace_mod.phases()[-2:]
    assert weights["attrs"] == first["attrs"] == {"plane": "actor"}
    assert weights["t_mono"] + weights["dur_s"] <= first["t_mono"] and first["dur_s"] > 0.05


def test_three_epochs_of_a_learner_add_no_phase(phase_list, tmp_path, monkeypatch):
    """The device-replay ``Learner``: its construction is a phase (with the
    context and the state inside it); from its first epoch's end to its
    fourth's, rollouts, ingests, updates, evals and epoch boundaries add none."""
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    cfg = normalize_args({
        "env_args": {"env": "HungryGeese"},
        "train_args": {
            "turn_based_training": False, "observation": False, "batch_size": 8,
            "forward_steps": 8, "minimum_episodes": 10, "update_episodes": 30,
            "maximum_episodes": 1000, "epochs": 4, "eval_rate": 0.0,
            "device_rollout_games": 8, "device_replay": True, "device_replay_slots": 256,
            "device_replay_k_steps": 16, "mesh": {"dp": 1}, "worker": {"num_parallel": 1},
        },
    })
    learner = Learner(cfg)
    built = [p["name"] for p in trace_mod.phases()]
    assert built[-1] == "setup.learner" and built.count("setup.learner") == 1
    assert {"setup.train_context", "setup.init_state"} <= set(built[:-1])
    outer = trace_mod.phases()[-1]
    assert all(outer["t_mono"] <= p["t_mono"] and
               p["t_mono"] + p["dur_s"] <= outer["t_mono"] + outer["dur_s"] + 1e-6
               for p in trace_mod.phases()[:-1] if p["name"] != "setup.net")
    after_epoch = {}
    thread = threading.Thread(target=learner.run, daemon=True)
    thread.start()
    deadline = time.monotonic() + 300
    while thread.is_alive() and time.monotonic() < deadline:
        after_epoch.setdefault(learner.model_epoch, _phases_so_far())
        time.sleep(0.05)
    thread.join(timeout=60)
    assert not thread.is_alive() and learner.model_epoch == 4
    after_epoch[4] = _phases_so_far()
    assert 1 in after_epoch, sorted(after_epoch)
    assert after_epoch[4] == after_epoch[1]
