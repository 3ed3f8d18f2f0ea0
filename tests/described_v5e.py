"""What the compiles for a described (not attached) TPU v5e share
(tests/test_chip_compile.py, tests/test_chip_compile_cells.py; not collected:
no ``test_`` prefix): the topology as fixtures, the compile cache held off
round every case of a file that imports ``_no_compile_cache``, and a train
step lowered for the described chips from shapes alone.  The topology is
described inside a fixture, never at import: each xdist worker that is given
one of those files loads the TPU's library then (the driver's command lets
several do so at once: ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``; without it a second
worker's fixture skips its file's cases).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e topology here: {exc}")


@pytest.fixture(scope="module")
def v5e(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip (the next run would warn and
    recompile), so the cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lowered(topo, dp, env_args, train_args, packed=None):
    """A train step of ``env_args``'s net under ``train_args``, lowered for a
    {dp: dp} mesh of the described chips from shapes alone; ``packed``
    (burn-in slots, forward slots) gives the batch the ``packed_order`` leaf
    ``put_batch`` makes for a net that takes one.  Returns (context, lowered)."""
    import random

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmark import traffic
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh, param_shardings

    cfg = normalize_args({"env_args": env_args, "train_args": train_args})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    batch_size = args["batch_size"]
    env = make_env(args["env"])
    module = env.net()
    mesh = make_mesh({"dp": dp}, devices=topo.devices)
    ctx = TrainContext(module, args, mesh)
    rows, rep = NamedSharding(mesh, PartitionSpec("dp")), NamedSharding(mesh, PartitionSpec())

    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    params = jax.eval_shape(
        lambda key: module.init(key, obs, module.initial_state((1,)))["params"],
        jax.random.PRNGKey(0),
    )
    state = {"params": params, "opt_state": jax.eval_shape(ctx.tx.init, params),
             "steps": jax.ShapeDtypeStruct((), jnp.int32)}
    layout = param_shardings(mesh, state)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), state, layout
    )
    # a two-window batch of random play gives every leaf's shape and dtype
    random.seed(0)
    np.random.seed(0)
    small = traffic.random_play_batches(env, module, dict(args, batch_size=2), 1, 4)[0]
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (batch_size,) + np.shape(x)[1:], np.asarray(x).dtype, sharding=rows),
        small,
    )
    if packed is not None:
        players = np.shape(small["action"])[2]
        batch["packed_order"] = {
            part: jax.ShapeDtypeStruct((batch_size, players, slots), jnp.int32, sharding=rows)
            for part, slots in zip(("burn_in", "forward"), packed)}
    lowered = jax.jit(
        ctx._step_fn, donate_argnums=(0,),
        in_shardings=(layout, rows, rep), out_shardings=(layout, rep),
    ).lower(state, batch, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep))
    return ctx, lowered
