"""The one general generator: everything a run feeds the system is made
here from ``--seed`` and the parameters in the cell's file.

* ``seeded_params``       weights, on the device, in one jitted call
* ``balance_routers``     a routed net's selection biases (``score_bias``) at
                          the published update rule's fixed point on the
                          staged batches (``window_loads`` counts the rows)
* ``random_play_batches`` training windows of seeded random play, through
                          the program's own episode -> window -> batch path
* ``observation_pool``    observations of seeded random-play games, the
                          sample a net is compared with its reference on

The caller seeds ``random`` and ``numpy.random`` with ``--seed`` first (the
program's Generator and environments draw from them).
"""

from __future__ import annotations

import random
from typing import Any, Dict, List


def seeded_params(module, env, seed: int):
    """Parameters of ``module`` from ``seed``: the module's own initializers
    under one ``jax.jit``, so nothing is made on the host or leaf by leaf.
    A kernel the module initializes to zero (GeeseNet's heads, so that
    self-play starts uniform) is drawn instead, a tenth of a dense
    layer's scale so that the heads stay of order 1 on the tower's large
    features: all-zero heads answer 0 to every observation, and a
    comparison with the reference on them would hold for any tower."""
    import jax
    import jax.numpy as jnp

    env.reset()
    obs = env.observation(env.players()[0])
    obs_b = jax.tree.map(lambda x: jnp.asarray(x)[None], obs)
    hidden = module.initial_state((1,))

    def init(key):
        k_init, k_fill = jax.random.split(key)
        params = module.init(k_init, obs_b, hidden)["params"]
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(k_fill, len(leaves))
        filled = []
        for leaf, k in zip(leaves, keys):
            if leaf.ndim >= 2:
                fan_in = max(leaf.size // leaf.shape[-1], 1)
                drawn = 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype) / jnp.sqrt(fan_in)
                leaf = jnp.where(jnp.all(leaf == 0), drawn, leaf)
            filled.append(leaf)
        return jax.tree.unflatten(treedef, filled)

    return jax.jit(init)(jax.random.PRNGKey(seed))


# what a routed layer adds to its scores before it takes the top k: it chooses
# only, no gradient reaches it, and the seeded weights hold it at zeros
ROUTER_BIAS = "score_bias"
# ``balance_routers``: an expert's first step, the miss (in logarithms of the
# share over the load) under which a step shrinks with it, and what a step
# grows by while the expert's sign stands; how near the uniform share every
# held expert has to come, the most rounds, and how many it makes after its
# best one before it gives up
BALANCE_STEP, BALANCE_NEAR, BALANCE_GROW = 2.0 ** -6, 0.5, 1.2
BALANCE_WITHIN, BALANCE_ROUNDS, BALANCE_PATIENCE = 0.1, 200, 24


def _bias_leaves(params):
    """(layer, leaf) of every routed layer's selection bias: the layer is the
    first key of the leaf's path, which the net's ``choices`` are keyed by."""
    import jax

    return [(path[0].key, leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
            if path[-1].key == ROUTER_BIAS]


def router_biases(params) -> Dict[str, Any]:
    """Every routed layer's selection bias, on the host, by the layer's name.
    Empty for a net with no routed layer."""
    import numpy as np

    return {layer: np.asarray(leaf) for layer, leaf in _bias_leaves(params)}


def with_router_biases(params, biases: Dict[str, Any]):
    """``params`` with every routed layer's selection bias replaced by
    ``biases``' (a layer it does not name keeps its own)."""
    import jax
    import jax.numpy as jnp

    def leaf(path, x):
        if path[-1].key == ROUTER_BIAS and path[0].key in biases:
            return jnp.asarray(biases[path[0].key], x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(leaf, params)


def same_biases(ours: Dict[str, Any], theirs: Dict[str, Any]) -> bool:
    """Whether two nets' selection biases (``router_biases``) are the same
    layers' and equal to the bit."""
    import numpy as np

    return ours.keys() == theirs.keys() and all(
        np.array_equal(ours[k], theirs[k]) for k in ours)


def in_compute_dtype(params, dtype):
    """``params`` as the train step's forward reads them under
    ``compute_dtype: <dtype>``: cast to bfloat16 where that is the dtype (the
    selection biases with them).  The one cast of the runner's set-up, its
    judged forward and ``limit_readings.py``."""
    import jax
    import jax.numpy as jnp

    if dtype != "bfloat16":
        return params
    return jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)


def window_loads(module, args: Dict[str, Any]):
    """A jitted ``(params, batch) -> {layer: (n_experts,) float32}``: the rows
    each expert of each routed layer is sent over a staged batch, counted
    over the ``choices`` the train step's own forward (``forward_prediction``
    on ``in_compute_dtype``'s parameters) hands out, burn-in steps with
    them, under the observation mask."""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.parallel.train_step import forward_prediction

    burn_in = int(args["burn_in_steps"])

    def loads(params, batch):
        sizes = {layer: leaf.shape[0] for layer, leaf in _bias_leaves(params)}
        chosen = forward_prediction(module, params, batch, args).get("choices", {})
        if burn_in and chosen:
            chosen = {k: jnp.concatenate([chosen["window_start"][k][:, :burn_in], v], axis=1)
                      for k, v in chosen["forward"].items()}
        seen = batch["observation_mask"] > 0                    # (B, T, P, 1)
        return {
            layer: ((picks[..., None] == jnp.arange(sizes[layer])).any(axis=-2) & seen)
            .sum(axis=(0, 1, 2)).astype(jnp.float32)
            for layer, picks in chosen.items()}

    return jax.jit(loads)


def balance_routers(params, loads_of, batches, held=slice(None)):
    """Every routed layer's ``score_bias`` at the fixed point of the family's
    own balancing rule on ``batches``, by the layer's name, and what it took.

    ``b_e += step_e x sign(mean load - load_e)`` over every expert of every
    routed layer at once (the published rule), a round a forward pass over
    the batches (``loads_of``: ``window_loads``', on ``params`` as the step
    reads them; a quarter of them until they settle, then all).  Set-up pays
    every round, so the step is an expert's own: it halves when the expert's
    sign turns, grows by a fifth while it does not (a deeper layer's tokens
    move while the layers before it settle), and within ``BALANCE_NEAR`` of
    the share (in logarithms) it shrinks with the miss.  The fixed point is
    the rule's.  A bias stays a value of its leaf's dtype, so the step's cast
    rounds nothing away.  It ends when every layer's ``held`` experts (a
    slice: the ones this chip computes, whose rows are a step's time) are
    within ``BALANCE_WITHIN`` of the uniform share, after ``BALANCE_ROUNDS``, or
    ``BALANCE_PATIENCE`` rounds after the best one, and hands back the best
    round's biases.  The arithmetic is the host's, on counts: the same seed and
    files give the same bias to the bit.  A net with no routed layer gives
    no bias, and rounds 0."""
    import time

    import jax
    import numpy as np

    t0 = time.perf_counter()
    kept = {layer: leaf.dtype for layer, leaf in _bias_leaves(params)}
    bias = {k: v.astype(np.float32) for k, v in router_biases(params).items()}
    if not bias:
        return {}, {"layers": 0, "rounds": 0}
    step = {k: np.full(v.shape, BALANCE_STEP) for k, v in bias.items()}
    turned = {k: np.zeros(v.shape) for k, v in bias.items()}        # an expert's last sign
    best, seeded, made = None, None, 0
    part = max(1, len(batches) // 4)    # a quarter of the batches until they settle
    while True:
        trial = with_router_biases(params, bias)
        total = None
        for batch in batches[:part]:
            got = loads_of(trial, batch)
            total = got if total is None else jax.tree.map(lambda a, b: a + b, total, got)
        loads = {k: np.asarray(v, np.float64) for k, v in jax.device_get(total).items()}
        # a held expert's load over the uniform share, by layer
        over = {k: v[held] / v.mean() for k, v in loads.items()}
        worst = max(float(np.abs(v - 1.0).max()) for v in over.values())
        most = max(float(v.max()) for v in over.values())
        if seeded is None:
            seeded = most
        if best is None or worst < best[0]:
            best = (worst, made, {k: v.copy() for k, v in bias.items()}, most)
        if (worst <= BALANCE_WITHIN or made >= BALANCE_ROUNDS
                or made - best[1] >= BALANCE_PATIENCE):
            if part == len(batches):
                break
            part, best = len(batches), None     # what ends it is read on all of them
            continue
        made += 1
        for k, v in loads.items():
            # how far under its share, in logarithms (an expert with no row: 8 shares)
            miss = np.clip(np.log(v.mean() / np.maximum(v, v.mean() / 8)) / BALANCE_NEAR, -1, 1)
            sign = np.sign(miss)
            step[k] = step[k] * np.where(
                sign * turned[k] < 0, 0.5, np.where(sign * turned[k] > 0, BALANCE_GROW, 1.0))
            turned[k] = np.where(sign != 0, sign, turned[k])
            moved = bias[k] + step[k] * miss
            # top-k sees differences alone: about zero, the leaf's dtype keeps the most of them
            bias[k] = (moved - moved.mean()).astype(kept[k]).astype(np.float32)
    worst, at, bias, most = best
    return bias, {
        "layers": len(bias), "rounds": made, "best_round": at,
        "settled": worst <= BALANCE_WITHIN, "within": BALANCE_WITHIN,
        # the worst held expert of any layer: over its uniform share as seeded (on
        # the first round's batches) and as balanced, and how far off it (under or
        # over) the balanced one is
        "seeded_load_over_share": seeded, "load_over_share": most, "worst_off_share": worst,
        "seconds": time.perf_counter() - t0,
    }


def balanced_params(module, params, args: Dict[str, Any], batches):
    """``params`` with the selection biases ``balance_routers`` finds for
    ``module`` on the staged ``batches`` (``args``: the train step's), the
    biases, and the note.  The held experts are the module's
    (``expert_offset``, ``experts_held``)."""
    if not _bias_leaves(params):        # no routed layer: no cast and no forward pass is owed
        return params, {}, {"layers": 0, "rounds": 0}
    first = getattr(module, "expert_offset", 0)
    held = getattr(module, "experts_held", None)
    import jax

    biases, note = balance_routers(
        jax.jit(in_compute_dtype, static_argnums=1)(params, args.get("compute_dtype")),
        window_loads(module, args), batches,
        slice(first, None if held is None else first + held))
    return with_router_biases(params, biases), biases, note


def _random_model(env, module):
    """Uniform play: the output spec is read from the module's shapes, so
    the net itself never runs on the host to learn them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from handyrl_tpu.models import RandomModel

    env.reset()
    obs = env.observation(env.players()[0])
    obs_b = jax.tree.map(lambda x: jnp.asarray(x)[None], obs)
    hidden = module.initial_state((1,))
    shapes = jax.eval_shape(
        lambda: module.apply(module.init(jax.random.PRNGKey(0), obs_b, hidden), obs_b, hidden)
    )
    return RandomModel({
        k: (tuple(v.shape[1:]), np.float32)
        for k, v in shapes.items() if k != "hidden" and v is not None
    })


def random_play_batches(env, module, args: Dict[str, Any], n_batches: int,
                        fill_episodes: int) -> List[Dict[str, Any]]:
    """``n_batches`` host batches of ``args['batch_size']`` windows sampled
    from ``fill_episodes`` episodes of uniform random play."""
    from handyrl_tpu.runtime import EpisodeStore, Generator, make_batch

    model = _random_model(env, module)
    store = EpisodeStore(max(64, fill_episodes))
    generator = Generator(env, args)
    gen_args = {"player": env.players(), "model_id": {p: 0 for p in env.players()}}
    while len(store) < fill_episodes:
        episode = generator.generate({p: model for p in env.players()}, gen_args)
        if episode is not None:
            store.extend([episode])
    batches = []
    for _ in range(n_batches):
        windows = []
        while len(windows) < args["batch_size"]:
            window = store.sample_window(
                args["forward_steps"], args["burn_in_steps"], args["compress_steps"])
            if window is not None:
                windows.append(window)
        batches.append(make_batch(windows, args))
    return batches


def observation_pool(env, size: int):
    """``size`` observations from games of uniform random legal play, every
    acting player's view of every step, as one stacked pytree."""
    import jax
    import numpy as np

    pool = []
    while len(pool) < size:
        env.reset()
        while not env.terminal() and len(pool) < size:
            actions = {}
            for player in env.turns():
                pool.append(env.observation(player))
                actions[player] = random.choice(env.legal_actions(player))
            env.step(actions)
    return jax.tree.map(lambda *xs: np.stack(xs), *pool[:size])
