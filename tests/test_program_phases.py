"""The phases inside the device programs carry ``jax.named_scope``s that a
device profile shows as components of an op's ``op_name``.

``jit__step`` puts its update under ``opt_update``; ``jit_replay_train`` its
sampler under ``sample`` (``sample_draw``, ``sample_rows``, ``sample_obs``
inside) and the same ``opt_update``; ``jit_device_rollout`` its scan body
under ``env_reset``, ``env_observe``, ``rollout_policy``, ``rollout_act``,
``env_step``.  The names are module-level constants beside the program names
(tests/test_program_names.py), which the benchmark's readers import
(``benchmark/layer_metrics/program_phases.py``).  Here each program is
compiled on the CPU at TicTacToe size and read as the benchmark reads a
profile, through ``benchmark.trace_reduce.scopes_of``; a scope is metadata
only, so the lowered text of the step does not know it is there, and
neither does the persistent compile cache's key: the last test shows why the
scoped programs are jitted with ``scoped_program_options``.
"""

import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import trace_reduce
from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.envs.vector_parallel_tictactoe import VectorParallelTicTacToe
from handyrl_tpu.models import init_variables
from handyrl_tpu.parallel import TrainContext, make_mesh, train_step
from handyrl_tpu.runtime import device_eval, device_replay, device_rollout
from handyrl_tpu.utils.compile_cache import scoped_program_options

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LANES, K_STEPS, SLOTS = 4, 12, 32

CONSTANTS = {
    "opt_update": (train_step, "UPDATE_SCOPE"),
    "sample": (device_replay, "SAMPLE_SCOPE"),
    "sample_draw": (device_replay, "SAMPLE_DRAW_SCOPE"),
    "sample_rows": (device_replay, "SAMPLE_ROWS_SCOPE"),
    "sample_obs": (device_replay, "SAMPLE_OBS_SCOPE"),
    "env_reset": (device_rollout, "RESET_SCOPE"),
    "env_observe": (device_rollout, "OBSERVE_SCOPE"),
    "rollout_policy": (device_rollout, "POLICY_SCOPE"),
    "rollout_act": (device_rollout, "ACT_SCOPE"),
    "env_step": (device_rollout, "STEP_SCOPE"),
}
SCOPES = tuple(CONSTANTS)
SAMPLE_PARTS = ("sample_draw", "sample_rows", "sample_obs")
# program -> the scopes its ops carry, and whether they sit in a scan's body
PROGRAMS = {
    "step": (("opt_update",), False),
    "train": (("sample",) + SAMPLE_PARTS + ("opt_update",), True),
    "stream": (device_rollout.STREAM_SCOPES, True),
    "eval": ((), True),
}
# neither is differentiated: none of their ops may sit in the backward pass
FORWARD_ONLY = ("opt_update", "sample") + SAMPLE_PARTS


def _closure(fn, name):
    return fn.__closure__[fn.__code__.co_freevars.index(name)].cell_contents


def _op_names(lowered):
    return re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())


def _build():
    """ParallelTicTacToe at 4 lanes on a dp=2 mesh (on one CPU device the
    fused update's scan is unrolled, and there is no ``while/body`` to find
    a scope in): the rollout run three times into a 32-slot replay."""
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
    mesh = make_mesh({"dp": 2})
    stream = device_rollout.build_streaming_fn(
        venv, module, LANES, K_STEPS, mesh=mesh, use_observe_mask=False)
    replay = device_replay.DeviceReplay(venv, module, args, mesh, LANES, slots=SLOTS)
    state = venv.init(LANES, jax.random.PRNGKey(3))
    hidden = module.initial_state((LANES, venv.num_players))
    key = jax.random.PRNGKey(4)
    for _ in range(3):
        key, sub = jax.random.split(key)
        state, hidden, records = stream(params, state, hidden, sub)
        replay.ingest_counted(records)
    return dict(venv=venv, module=module, params=params, args=args, mesh=mesh,
                stream=stream, replay=replay, state=state, hidden=hidden, key=key)


@pytest.fixture(scope="module")
def tiny():
    return _build()


def _lower_step(tiny):
    ctx = TrainContext(tiny["module"], tiny["args"], tiny["mesh"])
    state = ctx.init_state(tiny["params"])
    batch = ctx.put_batch(tiny["replay"].sample(jax.random.PRNGKey(7), 4))
    return ctx._bind(state).lower(state, batch, jnp.float32(1e-5))


def _lower_train(tiny):
    """``train_fn`` jits inside a closure on its first call: run it once,
    then lower the jitted function it holds."""
    ctx = TrainContext(tiny["module"], tiny["args"], tiny["mesh"])
    train = tiny["replay"].train_fn(ctx, fused_steps=2)
    state, metrics = train(ctx.init_state(tiny["params"]), jax.random.PRNGKey(5), 1e-5)
    assert np.isfinite(float(jax.device_get(metrics["total"])))
    return _closure(train, "holder")["fn"].lower(
        state, tiny["replay"].rings, tiny["key"], jnp.float32(1e-5))


def _lower_stream(tiny):
    return tiny["stream"].lower(tiny["params"], tiny["state"], tiny["hidden"], tiny["key"])


def _lower_eval(tiny):
    fn = device_eval.build_eval_stream_fn(
        tiny["venv"], tiny["module"], LANES, K_STEPS, opponent="random")
    seat = np.zeros((LANES,), np.int32)
    return fn.lower(tiny["params"], tiny["state"], tiny["hidden"], seat, tiny["key"])


LOWER = {"step": _lower_step, "train": _lower_train, "stream": _lower_stream,
         "eval": _lower_eval}


@pytest.fixture(scope="module")
def compiled(tiny):
    """program -> the ``op_name`` of each op of its compiled module."""
    found = {}

    def of(program):
        if program not in found:
            found[program] = _op_names(LOWER[program](tiny))
        return found[program]

    return of


@pytest.fixture(scope="module")
def unscoped():
    """The ``op_name``s of the four programs built and compiled with this
    file's scopes taken out: what is left must read as no scope at all."""
    real = jax.named_scope
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "named_scope", lambda name: (
        contextlib.nullcontext() if name in SCOPES else real(name)))
    try:
        bare = _build()
        return [name for program in LOWER for name in _op_names(LOWER[program](bare))]
    finally:
        patch.undo()


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_program_carries_its_scopes_and_no_other(program, compiled):
    names = compiled(program)
    assert names
    mine, in_a_scan = PROGRAMS[program]
    found = {}
    for name in names:
        for scope in trace_reduce.scopes_of(name, SCOPES):
            found.setdefault(scope, []).append(name)
    # device eval steps the same env as the rollout and carries none of its
    # scopes: they sit in the rollout's body, not in the env
    assert set(found) == set(mine), sorted(found)
    for scope, ops in found.items():
        if in_a_scan:
            assert any("while/body" in name for name in ops), (scope, ops[:3])
        if scope in FORWARD_ONLY:
            assert not [name for name in ops if "transpose(" in name], scope
        if scope in SAMPLE_PARTS:
            assert all(trace_reduce.scopes_of(name, ("sample",)) for name in ops), scope
    if program in ("step", "train"):
        # the backward pass is there, and outside every scope of this file
        assert any("transpose(" in name for name in names)


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_name_is_a_constant_nothing_else_bears(scope, unscoped):
    owner, constant = CONSTANTS[scope]
    assert getattr(owner, constant) == scope
    # a jax primitive is the last component of every op's name, a jitted
    # helper (``_take``, ``_where``) or a flax submodule one in the middle
    assert len(unscoped) > 1000
    assert not [name for name in unscoped if trace_reduce.scopes_of(name, (scope,))]
    primitives = {
        value.name for module in (jax.lax, jax._src.prng, jax._src.random, jax._src.ad_util)
        for value in vars(module).values() if isinstance(value, jax.extend.core.Primitive)}
    assert len(primitives) > 100 and scope not in primitives
    assert scope not in _submodule_names()


_NETS = {}


def _submodule_names():
    """The flax module and parameter names of the nets the benchmark's cells
    build: ``GeeseNet``, ``TransformerNet`` and ``HybridNet`` (the last two
    at the rehearsals' tiny widths: names do not go with the width)."""
    if _NETS:
        return _NETS["names"]
    tiny = os.path.join(REPO, "benchmark", "tests")
    env_args = [{"env": "HungryGeese"}]
    for folder, name in (("tiny", "tiny_xfmr"), ("tiny_hybrid", "tiny_hybrid")):
        with open(os.path.join(tiny, folder, "configs", name + ".json")) as f:
            env_args.append(json.load(f)["env_args"])
    names, built = set(), []
    for one in env_args:
        cfg = normalize_args({"env_args": dict(one), "train_args": {}})
        env = make_env(cfg["env_args"])
        module = env.net()
        built.append(type(module).__name__)
        shapes = jax.eval_shape(lambda m=module, e=env: init_variables(m, e))
        for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            names.update(str(getattr(key, "key", key)) for key in path)
    assert built == ["GeeseNet", "TransformerNet", "HybridNet"]
    _NETS["names"] = names
    return names


def test_scope_names_are_distinct_and_one_program_each():
    assert len(set(SCOPES)) == len(SCOPES) == 10
    assert set(device_rollout.ENV_SCOPES) < set(device_rollout.STREAM_SCOPES)
    # but for the update, which every train program gets from ``_step``, no
    # scope is in two programs of one cell
    stream, train = set(PROGRAMS["stream"][0]), set(PROGRAMS["train"][0])
    assert not stream & train


def test_step_lowers_to_the_same_text_without_its_scope(tiny, monkeypatch):
    """A scope writes op metadata (debug locations, which ``as_text`` leaves
    out) and nothing else: the computation is the one without it."""
    scoped = _lower_step(tiny).as_text()
    real, entered = jax.named_scope, []

    def without(name):
        if name in SCOPES:
            entered.append(name)
            return contextlib.nullcontext()
        return real(name)

    monkeypatch.setattr(jax, "named_scope", without)
    assert _lower_step(tiny).as_text() == scoped
    # the patch was in force: the step asked for its scope (the eager
    # ``replay.sample`` that makes the batch for the sampler's) and got none
    assert entered.count("opt_update") == 1


def test_a_scope_alone_loads_the_executable_compiled_before(tmp_path):
    """jax keys its persistent cache by the computation without locations,
    where a scope lives: a program that only renamed a scope loads the
    executable compiled before and shows the old names (the chip did so with
    every scope of this file, PERF.md section 6, PR 39).  The scopes' names
    in a compile option give it a key of its own."""
    from jax.experimental.compilation_cache import compilation_cache

    def build(scope, *named):
        def toy(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0
        return jax.jit(toy, compiler_options=scoped_program_options(*named))

    def scopes_seen(fn):
        names = _op_names(fn.lower(jnp.arange(8.0)))
        return {scope for name in names
                for scope in trace_reduce.scopes_of(name, ("phase_one", "phase_two"))}

    # the cache's path option, in two parts: tests/test_chip_smoke.py holds
    # that only the helper names it, and this case needs a cache of its own
    keys = ("jax_compilation_cache" + "_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {key: getattr(jax.config, key) for key in keys}
    try:
        for key, value in zip(keys, (str(tmp_path), 0.0, -1, True)):
            jax.config.update(key, value)
        compilation_cache.reset_cache()
        assert scopes_seen(build("phase_one")) == {"phase_one"}
        assert scopes_seen(build("phase_two")) == {"phase_one"}      # the cache's
        assert scopes_seen(build("phase_two", "phase_two")) == {"phase_two"}
        assert scopes_seen(build("phase_one", "phase_one")) == {"phase_one"}
    finally:
        for key, value in before.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
    assert scoped_program_options("a", "b") != scoped_program_options("a", "c")
    assert scoped_program_options("a", "b") == scoped_program_options("a", "b")
