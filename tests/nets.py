"""What the tests of ``HybridNet``'s families share (``tests/test_*_net.py``;
not collected: no ``test_`` prefix): the plain references loaded from
``benchmark/``, a family's tiny net and its configuration, seeded parameters,
a window run in both modes, the distance two sets of heads are apart, the
Geister entry point with ``forward_prediction`` jitted once a (net, arguments)
pair, and (``_env_batch``) any environment's net with a batch of random play.
A family is the ``NET`` a test file states and what its copies of these
helpers used to differ by; the two families that several files share
(``HYBRID``, ``ZAYA``) are stated here.

A net, its seeded parameters and its jitted window are built once a
(family, net arguments) key and handed to every case that asks again
(``functools.lru_cache``: a flax module is hashable by its fields), so the
cases of a file share one trace and one compile.  What is handed out is
immutable (``jax.Array`` leaves in a fresh dict a call): a case that edits its
parameters edits a copy.  A case that patches the program or the reference
(``monkeypatch``) asks for ``fresh=True``: a program traced under the patch,
kept by no one."""

import dataclasses
import functools
import importlib.util
import json
import os
import random
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import HybridNet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS = ("policy", "value", "return")
# the train step's scan over step mode as the chip runs it, a rolled
# ``lax.scan``: unrolled, as ``unroll: auto`` has it on one CPU device, a short
# window's backward pass is minutes of XLA:CPU compile (94 s against 5 at 18
# steps of the looped net: PR 67)
SCAN = {"seq_forward": False, "unroll": False}


@functools.lru_cache(maxsize=None)
def _load(*parts):
    """``benchmark/<parts>`` as a module of its own (the references and the
    flop counts import nothing from ``handyrl_tpu.models``), once a process."""
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("_".join(parts)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """One test file's tiny net: ``net`` its ``net_args``, ``reference`` the
    plain reference's file under ``benchmark/reference/``, ``lively`` what
    moves fresh parameters off their initial zeros and ones ((params, seed) ->
    params), ``on_geister`` the ``net_args`` that differ where whole Geister
    windows go through it, ``rows`` x ``steps`` its toy window, of which each
    step is observed with probability ``observed``."""
    name: str
    net: dict
    reference: str
    lively: Optional[Callable] = None
    on_geister: dict = dataclasses.field(default_factory=dict)
    actions: int = 7
    rows: int = 3
    steps: int = 14
    observed: float = 0.6

    @property
    def REFERENCE(self):
        return _load("reference", self.reference)


def _config(family, **net):
    return {"name": family.name, "env_args": {"env": "Geister", "net": "hybrid",
                                              "net_args": dict(family.net, **net)}}


def _module(family, **net):
    return HybridNet(num_actions=family.actions, with_return=True, **dict(family.net, **net))


def _lively(params, seed, routers, bias_noise):
    """Every vector leaf (biases, norm scales, ``score_bias`` and a family's
    own) moved off its initial zeros or ones, so that leaving one out shows,
    and the matrices named in ``routers`` scaled up ({name: factor}), so that
    the scores, and not the choosing bias, spread the tokens over the experts."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(paths))

    def moved(path, leaf, key):
        name = path[-1].key
        if name in routers:
            return routers[name] * leaf
        noise = bias_noise if name == "score_bias" else 0.3
        return leaf + noise * jax.random.normal(key, leaf.shape) if leaf.ndim == 1 else leaf

    return jax.tree.unflatten(treedef, [moved(p, l, k) for (p, l), k in zip(paths, keys)])


@functools.lru_cache(maxsize=None)
def _seeded(family, module):
    return jax.jit(lambda seed: family.lively(
        module.init(jax.random.PRNGKey(seed), {"a": jnp.ones((family.rows, 5))},
                    module.initial_state((family.rows,)))["params"], seed + 5))


def _init(family, module, seed=0):
    """``module``'s lively parameters from ``seed``, traced and compiled once
    a net: every case that wants weights of a net shares the program built
    here, and gets a dict of its own."""
    return _seeded(family, module)(seed)


def _inputs(family):
    """(obs, key_mask) of the family's toy window, from fixed keys."""
    obs = {"a": jax.random.normal(jax.random.PRNGKey(1), (family.rows, family.steps, 5))}
    mask = jax.random.uniform(jax.random.PRNGKey(3), (family.rows, family.steps)) < family.observed
    return obs, mask.astype(jnp.float32)


def _toy(family):
    """(module, params, obs, mask, the reference's outputs) of the family's net as stated."""
    module = _module(family)
    obs, mask = _inputs(family)
    params = _init(family, module)
    return module, params, obs, mask, _reference(family, params, obs, mask)


def _random_window(seed, rows=3, steps=10, width=7, observed=0.6):
    """(obs, key_mask): ``rows`` sequences of ``steps`` steps, each step
    observed with probability ``observed``."""
    rng = np.random.RandomState(seed)
    obs = {"a": jnp.asarray(rng.randn(rows, steps, width), jnp.float32)}
    return obs, jnp.asarray(rng.rand(rows, steps) < observed, jnp.float32)


@functools.lru_cache(maxsize=None)
def _fresh(module):
    return jax.jit(lambda key, obs: module.init(key, obs, None)["params"])


def _params(module, obs, seed=0):
    """``module``'s fresh parameters for windows like ``obs``: one compiled
    program a net, where an eager ``init`` builds one an operation."""
    return _fresh(module)(jax.random.PRNGKey(seed), jax.tree.map(lambda x: x[:, 0], obs))


@functools.lru_cache(maxsize=None)
def _windowed(module, how):
    return jax.jit(lambda p, o, m, order: module.apply(
        {"params": p}, o, None, seq=True, key_mask=m, packed_order=order, **dict(how)))


def _window(module, params, obs, mask, packed_order=None, fresh=False, **how):
    """Window mode in float32 under ``highest``; ``how`` (``burn_in``,
    ``remat``) picks the program, one a (net, ``how``) pair."""
    build = _windowed.__wrapped__ if fresh else _windowed
    with jax.default_matmul_precision("highest"):
        return build(module, tuple(sorted(how.items())))(params, obs, mask, packed_order)


@functools.lru_cache(maxsize=None)
def _referenced(family, net, forced):
    config = _config(family, **dict(net))
    forward = family.REFERENCE.forward
    if forced:
        return jax.jit(lambda p, o, m, c: forward(p, o, m, config, choices=c))
    return jax.jit(lambda p, o, m, c: forward(p, o, m, config))


def _reference(family, params, obs, mask, choices=None, fresh=False, **net):
    """The family's plain reference on the window, its own choices or those handed to it."""
    build = _referenced.__wrapped__ if fresh else _referenced
    with jax.default_matmul_precision("highest"):
        return build(family, tuple(sorted(net.items())), choices is not None)(
            params, obs, mask, choices)


def _apart(got, want, mask):
    """Largest difference over the observed steps, in units of a head's scale."""
    worst = 0.0
    for head in HEADS:
        a, b = np.asarray(got[head], np.float32), np.asarray(want[head], np.float32)
        diff = np.abs(a - b) * np.asarray(mask)[..., None]
        worst = max(worst, float(diff.max()) / max(1.0, float(np.abs(b).max())))
    return worst


@functools.lru_cache(maxsize=None)
def _stepped(module, rows):
    def step(params, hidden, obs_t, seen):
        out = module.apply({"params": params}, obs_t, hidden)
        new = out.pop("hidden")
        return jax.tree.map(lambda old, fresh: jnp.where(
            seen.reshape((rows,) + (1,) * (old.ndim - 1)) > 0, fresh, old), hidden, new), out, new

    return jax.jit(step)


def _scan(module, params, obs, mask, count_every_step=False):
    """Step mode over the window by hand, as the train step's scan path does
    it: the hidden state is committed only where a step was observed.
    ``count_every_step`` is a fault: the position moves on unobserved steps
    too.  -> (heads, the hidden state it ends with)."""
    rows, steps = mask.shape
    step = _stepped(module, rows)
    hidden, outs = module.initial_state((rows,)), []
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            hidden, out, new = step(params, hidden, jax.tree.map(lambda x: x[:, t], obs), mask[:, t])
            if count_every_step:
                hidden = dict(hidden, pos=new["pos"])
            outs.append(out)
    return {head: jnp.stack([o[head] for o in outs], axis=1) for head in HEADS}, hidden


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _geister(family, train_args, seed=1, **net):
    """(config, args, env, ``env.net()``) of the family's net on Geister."""
    config = _config(family, **dict(family.on_geister, **net))
    cfg = normalize_args({"env_args": dict(config["env_args"]),
                          "train_args": dict(train_args, observation=True, seed=seed)})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(seed)
    np.random.seed(seed)
    env = make_env(args["env"])
    return config, args, env, env.net()


@functools.lru_cache(maxsize=None)
def _geister_windows(family, batch_size, burn_in_steps, forward_steps, **net):
    """(config, args, module, seeded parameters, one batch of random-play
    windows), made once: Geister's players observe on their own turns, so
    unobserved steps abound."""
    from benchmark import traffic

    config, args, env, module = _geister(family, {
        "batch_size": batch_size, "burn_in_steps": burn_in_steps, "forward_steps": forward_steps}, **net)
    assert isinstance(module, HybridNet) and module.with_return
    assert module.pattern == dict(family.net, **net)["pattern"]
    params = traffic.seeded_params(module, env, 1)
    batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
    assert 0.2 < float(np.mean(batch["observation_mask"])) < 0.8
    return config, args, module, params, batch


_PREDICTIONS = {}


def _predict(module, args, **over):
    """Jitted (params, batch) -> ``forward_prediction`` under ``args`` (with
    ``over`` laid over them): one program a (net, arguments) pair, whichever
    case asks."""
    from handyrl_tpu.parallel.train_step import forward_prediction

    args = dict(args, **over)
    key = (module, json.dumps(args, sort_keys=True, default=repr))
    if key not in _PREDICTIONS:
        _PREDICTIONS[key] = jax.jit(lambda p, b: forward_prediction(module, p, b, args))
    return _PREDICTIONS[key]


def _bf16_loss_and_grads(module, params, obs, mask, remat, burn_in):
    """((loss over the value and policy heads, counters), every leaf's
    gradient) of the window, weights and stream in bfloat16 (the experts'
    products are then the grouped kernel's, in the interpreter); a new
    program a call, so that a case may patch the net between two."""
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731

    def loss(p):
        out = module.apply({"params": to(p, jnp.bfloat16)}, to(obs, jnp.bfloat16), None, seq=True,
                           key_mask=mask, burn_in=burn_in, remat=remat)
        return (jnp.sum(jnp.square(out["value"].astype(jnp.float32) * mask[..., None]))
                + 0.1 * jnp.sum(out["policy"].astype(jnp.float32) * mask[..., None]),
                out["counters"])

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def _rows_stepped_in_place(module, params, obs):
    """Step mode with ``rows`` against step mode on the acting rows gathered by
    hand: the heads, the acting player's leaves where they lie (as zeros where
    the row's game has just begun), the other player's left as they were, or
    zeroed where it begins."""
    rows = obs["a"].shape[0]
    assert all(jax.tree.leaves(module.rows_in_place(
        {"layers": module.initial_state((1,))["layers"]})))
    filled = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(x.size), x.shape),
        module.initial_state((rows, 2)))
    filled["pos"] = jnp.array([[3.0, 1.0], [7.0, 2.0], [0.0, 5.0]])
    player, begun = jnp.array([1, 0, 1], jnp.int32), jnp.array([False, False, True])
    step_obs = {"a": obs["a"][:, 0]}
    lanes = jnp.arange(rows)
    acting = jax.tree.map(lambda x: x[lanes, player] * ~begun.reshape(
        (-1,) + (1,) * (x.ndim - 2)), filled)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h: module.apply({"params": params}, step_obs, h))(acting)
        got = jax.jit(lambda h, r: module.apply({"params": params}, step_obs, h, rows=r))(
            dict(filled, pos=acting["pos"]), (player, begun))
    for head in HEADS:
        np.testing.assert_allclose(got[head], want[head], atol=1e-5)
    for new, old, stepped in zip(got["hidden"]["layers"], filled["layers"],
                                 want["hidden"]["layers"]):
        for name in new:
            np.testing.assert_allclose(new[name][lanes, player], stepped[name], atol=1e-5)
            rest = np.array(old[name][lanes, 1 - player])
            rest[np.asarray(begun)] = 0.0
            np.testing.assert_array_equal(new[name][lanes, 1 - player], rest)


def _eight_bit_readings(family, module, obs, mask):
    """(sound, rough): over three seeds, how far bfloat16 weights and stream
    are from the reference forced to their choices, and how far the same
    weights rounded leaf by leaf to float8 e4m3 first; one traced forward for
    the six readings, and the reference every forced case shares."""
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731
    sound, rough = [], []
    forward = jax.jit(lambda w: module.apply(
        {"params": w}, to(obs, jnp.bfloat16), None, seq=True, key_mask=mask))
    for seed in range(3):
        p = _init(family, module, seed)
        for weights, readings in ((to(p, jnp.bfloat16), sound),
                                  (to(to(p, jnp.float8_e4m3fn), jnp.bfloat16), rough)):
            got = forward(weights)
            want = _reference(family, p, obs, mask, choices=got["choices"])
            readings.append(_apart(got, want, mask))
    return sound, rough


def _both_paths_on_geister(family, windows, burn_in=4):
    """``forward_prediction`` through ``env.net()`` on the Geister windows: the
    whole-window call and the train step's scan over step mode, both within
    1e-4 of ``forward_rows`` forced to the window's choices, over legal logits
    and observed values.  -> the window path's outputs."""
    config, args, module, params, batch = windows
    with jax.default_matmul_precision("highest"):
        window = _predict(module, args, seq_forward=True)(params, batch)
        scan = _predict(module, args, **SCAN)(params, batch)
        want = jax.jit(lambda p, b, c: family.REFERENCE.forward_rows(
            p, b, config, burn_in, choices=c))(params, batch, window["choices"])
    observed = batch["observation_mask"][:, burn_in:]
    legal = (batch["action_mask"][:, burn_in:] == 0) & (batch["turn_mask"][:, burn_in:] > 0)
    for head in HEADS:
        keep = legal if head == "policy" else observed > 0
        for got in (window, scan):
            diff = np.where(keep, np.asarray(got[head]) - np.asarray(want[head]) * (
                1 if head == "policy" else observed), 0.0)
            assert float(np.abs(diff).max()) < 1e-4, head
    return window


def _update_and_checkpoint(windows, tmp_path, clear=()):
    """One traced ``TrainContext`` update of the Geister windows under ``remat:
    block`` (``clear``: process-wide records emptied first, so that the trace
    holds this step's alone): finite, and the state saved and loaded is the
    state.  -> (metrics, ``moved(*path)``: whether a parameter changed, the
    trace's records)."""
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime import checkpoint
    from handyrl_tpu.utils import trace

    _, args, module, params, batch = windows
    for record in clear:
        record.clear()
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        ctx = TrainContext(module, dict(args, seq_forward=True, remat="block"), make_mesh({"dp": 1}))
        before = jax.device_get(params)
        state, metrics = ctx.train_step(ctx.init_state(params), ctx.put_batch(batch), 1e-3)
        metrics, after = jax.device_get(metrics), jax.device_get(state["params"])
    finally:
        trace.shutdown()
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    checkpoint.save_train_state(str(tmp_path / "state.ckpt"), state)
    loaded = checkpoint.load_train_state(str(tmp_path / "state.ckpt"), jax.device_get(state))
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(jax.device_get(state))):
        np.testing.assert_array_equal(a, b)
    moved = lambda *path: not np.allclose(  # noqa: E731
        np.asarray(_at(after, path)), np.asarray(_at(before, path)))
    return metrics, moved, trace.read_trace(str(tmp_path / "trace.jsonl"))


def _env_batch(env_args, train_overrides):
    """(module, variables, one batch of 8 windows of random play, args) of any
    environment's net under ``train_overrides``: what tests/test_parallel.py
    and tests/test_parallel_grad_sync.py step."""
    from handyrl_tpu.models import InferenceModel, RandomModel, init_variables
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    # pin the GLOBAL random stream: episode generation below draws from
    # it, and inheriting whatever state earlier in-process tests left
    # (learner/league e2es make a timing-dependent number of draws)
    # makes the numeric-tolerance tests downstream load-flaky — the bf16
    # delta bound was observed failing only under full-suite load
    random.seed(20260804)

    cfg = normalize_args({"env_args": env_args, "train_args": {
        "batch_size": 8, "forward_steps": 4, "compress_steps": 4, **train_overrides}})
    args = dict(cfg["train_args"], env=cfg["env_args"])

    env = make_env(args["env"])
    module = env.net()
    variables = init_variables(module, env)
    model = InferenceModel(module, variables)
    env.reset()
    random_model = RandomModel.from_model(model, env.observation(env.players()[0]))

    store = EpisodeStore(64)
    gen = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < 4:
        ep = gen.generate({p: random_model for p in env.players()}, gen_args)
        if ep is not None:
            store.extend([ep])
    windows = []
    while len(windows) < args["batch_size"]:
        w = store.sample_window(args["forward_steps"], args["burn_in_steps"], args["compress_steps"])
        if w is not None:
            windows.append(w)
    return module, variables, make_batch(windows, args), args


# the family of tests/test_hybrid_net.py, which the files cut from it share
# (test_hybrid_packed.py, test_hybrid_step.py, test_routed_experts.py)
HYBRID = Family(
    "tiny_hybrid", dict(
        pattern="MEM*E", d_model=32, norm_eps=1e-5,
        mamba_heads=4, mamba_head_dim=16, n_groups=2, state_size=16, conv_kernel=4, chunk=4,
        n_experts=8, top_k=2, expert_width=32, shared_width=64, routed_scale=2.5,
        experts_held=4, expert_offset=2,
        n_heads=4, n_kv_heads=2, head_dim=16, memory_len=200,
    ), "nemotron_twotower_30b_a3b.py", actions=5)


# the family of tests/test_zaya_net.py and tests/test_zaya_stack.py; ``lively``: the routers'
# last maps scaled up, ``carry_scale`` and ``temp`` moved with every vector
ZAYA = Family(
    "tiny_zaya", dict(
        pattern="CECECE", d_model=32, norm_eps=1e-5,
        n_heads=4, n_kv_heads=2, head_dim=8, memory_len=6, rope_theta=1e4, rotary_factor=0.5,
        cca_time0=2, cca_time1=2,
        n_experts=8, top_k=1, expert_width=16, shared_width=0, routed_scale=1.0,
        experts_held=4, expert_offset=0, router="mlp", router_width=8, gated_experts=True,
    ), "zaya1_8b.py", on_geister={"memory_len": 200},
    lively=functools.partial(_lively, routers={"router_out": 8}, bias_noise=0.03))

# the family of tests/test_kanana_net.py and tests/test_kanana_periods.py: a value narrower
# than the unrotated key part, as the published 128 is narrower than 192 (a head split at the
# wrong place shows); ``lively``: the routers scaled up, ``kv_norm`` moved with every vector
KANANA = Family(
    "tiny_kanana", dict(
        pattern="L-LELE", d_model=32, norm_eps=1e-6,
        n_heads=4, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6, kv_latent=12, memory_len=6,
        rope_theta=1e4, mlp_width=48,
        n_experts=8, top_k=3, expert_width=16, shared_width=24, routed_scale=2.448,
        experts_held=4, expert_offset=0, router="sigmoid", gated_experts=True,
    ), "kanana_2_30b_a3b.py", on_geister={"memory_len": 200},
    lively=functools.partial(_lively, routers={"router": 4}, bias_noise=0.03))
