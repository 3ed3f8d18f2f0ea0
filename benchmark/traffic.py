"""The one general generator: everything a run feeds the system is made
here from ``--seed`` and the parameters in the cell's file.

* ``seeded_params``       weights, on the device, in one jitted call
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
