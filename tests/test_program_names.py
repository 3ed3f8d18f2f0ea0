"""The loop's device programs carry stable XLA module names, and the
rollout and trainer threads' waits are spanned and counted.

A device profile shows a program as ``jit_<fn.__name__>``; the rollout, the
fused sample+train step, the host sampler and device eval all jitted an
inner function called ``fn``, so a trace could not tell them apart.  Part
one pins one name per program (the constants the benchmark's readers
import); part two runs the tiny device-replay ``Learner`` once with tracing
on and checks what it left in ``trace.jsonl`` and ``metrics.jsonl``.
"""

import json
import logging
import os
import re

import jax
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.envs.vector_parallel_tictactoe import VectorParallelTicTacToe
from handyrl_tpu.envs.vector_tictactoe import VectorTicTacToe
from handyrl_tpu.models import init_variables
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.runtime import device_eval, device_replay, device_rollout
from handyrl_tpu.utils import trace as trace_mod

LANES, K_STEPS, SLOTS = 4, 12, 32

CONSTANTS = {
    "stream": (device_rollout, "STREAM_PROGRAM", "device_rollout"),
    "episodes": (device_rollout, "EPISODE_PROGRAM", "device_rollout_episodes"),
    "train": (device_replay, "TRAIN_PROGRAM", "replay_train"),
    "sample": (device_replay, "SAMPLE_PROGRAM", "replay_sample"),
    "ingest": (device_replay, "INGEST_PROGRAM", "ingest"),
    "eval": (device_eval, "EVAL_PROGRAM", "device_eval"),
}


def _module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def tiny():
    """ParallelTicTacToe at 4 lanes: the streaming rollout run three times
    into a 32-slot replay, so every program of the loop has real inputs."""
    venv = VectorParallelTicTacToe
    cfg = normalize_args({
        "env_args": {"env": "ParallelTicTacToe"},
        "train_args": {"turn_based_training": False, "observation": False,
                       "batch_size": 4, "forward_steps": 4, "burn_in_steps": 0},
    })
    args = dict(cfg["train_args"], env=cfg["env_args"])
    env = make_env(cfg["env_args"])
    module = env.net()
    params = init_variables(module, env)["params"]
    mesh = make_mesh({"dp": 1})
    stream = device_rollout.build_streaming_fn(
        venv, module, LANES, K_STEPS, mesh=None, use_observe_mask=False)
    replay = device_replay.DeviceReplay(venv, module, args, mesh, LANES, slots=SLOTS)
    state = venv.init(LANES, jax.random.PRNGKey(3))
    hidden = module.initial_state((LANES, venv.num_players))
    key = jax.random.PRNGKey(4)
    for _ in range(3):
        key, sub = jax.random.split(key)
        state, hidden, records = stream(params, state, hidden, sub)
        replay.ingest_counted(records)
    return dict(venv=venv, module=module, params=params, args=args, mesh=mesh,
                stream=stream, replay=replay, state=state, hidden=hidden,
                records=records, key=key)


def _lower_stream(tiny):
    return tiny["stream"].lower(tiny["params"], tiny["state"], tiny["hidden"], tiny["key"])


def _lower_episodes(tiny):
    env = make_env({"env": "TicTacToe"})
    module = env.net()
    params = init_variables(module, env)["params"]
    fn = device_rollout.build_selfplay_fn(VectorTicTacToe, module, LANES)
    return fn.lower(params, tiny["key"])


def _lower_ingest(tiny):
    replay = tiny["replay"]
    return replay._ingest.lower(replay.rings, tiny["records"])


def _lower_eval(tiny):
    venv = tiny["venv"]
    fn = device_eval.build_eval_stream_fn(
        venv, tiny["module"], LANES, K_STEPS, opponent="random")
    seat = np.zeros((LANES,), np.int32)
    return fn.lower(tiny["params"], tiny["state"], tiny["hidden"], seat, tiny["key"])


LOWERED = {"stream": _lower_stream, "episodes": _lower_episodes,
           "ingest": _lower_ingest, "eval": _lower_eval}


@pytest.fixture(scope="module")
def compiled_names(tiny):
    """``train_fn`` and ``sample_host`` jit lazily inside a closure: run
    each once and read the names jax logs as it compiles them."""
    records = []

    class Catch(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler, logger = Catch(level=logging.DEBUG), logging.getLogger("jax")
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        with jax.log_compiles(True):
            ctx = TrainContext(tiny["module"], tiny["args"], tiny["mesh"])
            state = ctx.init_state(tiny["params"])
            train = tiny["replay"].train_fn(ctx, fused_steps=2)
            state, metrics = train(state, jax.random.PRNGKey(5), 1e-5)
            batch = tiny["replay"].sample_host(jax.random.PRNGKey(6), 4)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
    assert np.isfinite(float(jax.device_get(metrics["total"])))
    assert batch["action"].shape[0] == 4
    return set(re.findall(r"Compiling (?:jit\()?(\w+)", "\n".join(records)))


@pytest.mark.parametrize("role", sorted(CONSTANTS))
def test_program_has_its_own_module_name(role, tiny, request):
    owner, constant, want = CONSTANTS[role]
    assert getattr(owner, constant) == want
    if role in LOWERED:
        assert _module_name(LOWERED[role](tiny)) == "jit_" + want
    else:
        names = request.getfixturevalue("compiled_names")
        assert want in names, sorted(names)
        assert "fn" not in names, sorted(names)


def test_program_names_are_distinct():
    names = [getattr(owner, constant) for owner, constant, _ in CONSTANTS.values()]
    assert len(set(names)) == len(names)
    assert "fn" not in names


# ---------------------------------------------------------------------------
# the traced learner run
# ---------------------------------------------------------------------------

GEESE_LANES, GEESE_K = 8, 16


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """tests/test_device_replay.py::test_learner_device_replay_end_to_end's
    learner (8 lanes, 256 slots, 2 epochs) once, with tracing on and
    epochs long enough for the trainer to pipeline updates."""
    from handyrl_tpu.runtime.learner import Learner

    cwd = os.getcwd()
    os.chdir(tmp_path_factory.mktemp("traced_learner"))
    try:
        cfg = normalize_args({
            "env_args": {"env": "HungryGeese"},
            "train_args": {
                "turn_based_training": False, "observation": False,
                "batch_size": 8, "forward_steps": 8,
                # epochs of ~4 rollout dispatches, so that the trainer
                # pipelines several updates inside one
                "minimum_episodes": 10, "update_episodes": 100,
                "maximum_episodes": 1000, "epochs": 2, "eval_rate": 0.0,
                "device_rollout_games": GEESE_LANES, "device_replay": True,
                "device_replay_slots": 256, "device_replay_k_steps": GEESE_K,
                "worker": {"num_parallel": 1},
                "trace": {"enabled": True, "path": "trace.jsonl"},
            },
        })
        learner = Learner(cfg)
        assert learner.run() == 0
        trace_mod.shutdown()
        spans = [s for s in trace_mod.read_trace("trace.jsonl")
                 if s["name"] != trace_mod.META_NAME]
        with open("metrics.jsonl") as f:
            records = [json.loads(line) for line in f]
        return dict(spans=spans, records=records,
                    counters=dict(learner.rollout.replay.counters))
    finally:
        trace_mod.configure(None)   # disarmed and counted from zero again
        os.chdir(cwd)


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


@pytest.mark.parametrize(
    "name", ["rollout.dispatch", "rollout.ingest", "replay.stats_fetch", "rollout.submit"])
def test_rollout_spans_are_on_the_rollout_thread(traced_run, name):
    found = _named(traced_run["spans"], name)
    assert found, f"no {name} span in trace.jsonl"
    assert all(s["thread"].startswith("device-rollout-") for s in found), (
        sorted({s["thread"] for s in found}))


def test_stats_fetch_lies_inside_an_ingest(traced_run):
    """The deferred fetch of ingest N-1's stats happens inside
    ``ingest_counted`` of dispatch N; the tail's (``flush_counted``, as the
    thread ends) is the one fetch per thread with no ingest around it."""
    spans = traced_run["spans"]
    ingests = _named(spans, "rollout.ingest")
    outside = {}
    for fetch in _named(spans, "replay.stats_fetch"):
        t0, t1 = fetch["t_mono"], fetch["t_mono"] + fetch["dur_s"]
        inside = any(
            i["thread"] == fetch["thread"]
            and i["t_mono"] - 1e-5 <= t0 and t1 <= i["t_mono"] + i["dur_s"] + 1e-5
            for i in ingests)
        if not inside:
            outside[fetch["thread"]] = outside.get(fetch["thread"], 0) + 1
            last = max(i["t_mono"] + i["dur_s"] for i in ingests
                       if i["thread"] == fetch["thread"])
            assert t0 >= last - 1e-5, "a fetch outside every ingest, not at the tail"
    assert all(n == 1 for n in outside.values()), outside


@pytest.mark.parametrize("name", ["train_step", "train.pipeline_block"])
def test_trainer_spans_are_on_the_trainer_thread(traced_run, name):
    found = _named(traced_run["spans"], name)
    assert found, f"no {name} span in trace.jsonl"
    assert {s["thread"] for s in found} == {"trainer"}


def test_every_update_but_an_epochs_first_blocks_on_the_one_before(traced_run):
    """One-deep pipelining: an epoch of n updates (closed by one
    ``epoch.metrics_fetch``) blocks n - 1 times."""
    spans = traced_run["spans"]
    count = lambda name: len(_named(spans, name))  # noqa: E731
    assert count("train.pipeline_block") == (
        count("train_step") - count("epoch.metrics_fetch"))


def test_dispatch_wait_nests_inside_rollout_dispatch(traced_run):
    spans = traced_run["spans"]
    outer = _named(spans, "rollout.dispatch")
    inner = [s for s in _named(spans, "dispatch.run")
             if s["thread"].startswith("device-rollout-")]
    nested = [
        s for s in inner
        if any(o["t_mono"] - 1e-5 <= s["t_mono"]
               and s["t_mono"] + s["dur_s"] <= o["t_mono"] + o["dur_s"] + 1e-5
               for o in outer)]
    # one program run per rollout.dispatch (the ingest's runs lie outside)
    assert len(nested) == len(outer)


def test_no_span_was_dropped(traced_run):
    records = traced_run["records"]
    assert all(r.get("trace_dropped") == 0 for r in records)
    assert records[-1]["trace_spans"] > 0


def test_epoch_records_count_game_steps_and_dispatches(traced_run):
    records, counters = traced_run["records"], traced_run["counters"]
    steps = [r["device_game_steps"] for r in records]
    dispatches = [r["device_rollout_dispatches"] for r in records]
    assert steps == sorted(steps) and dispatches == sorted(dispatches)
    assert all(isinstance(v, int) for v in steps + dispatches)
    # every geese lane plays every step (finished lanes reset inside the
    # scan), so a dispatch books exactly lanes x k game steps
    per_dispatch = GEESE_LANES * GEESE_K
    assert steps == [n * per_dispatch for n in dispatches]
    # the rollout thread runs on after the last record and settles its
    # deferred tail as it stops: the record lags the final counters by
    # whole dispatches, never leads them
    assert 0 < dispatches[-1] <= counters["ingests"]
    assert counters["game_steps"] == counters["ingests"] * per_dispatch
