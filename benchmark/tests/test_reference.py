"""The plain references against the flax modules the system runs, on the
CPU at small sizes, and the operation counts against counts made by hand.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_reference.py -q
"""

import json
import os
import random
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import harness, traffic  # noqa: E402
from handyrl_tpu.config import normalize_args  # noqa: E402
from handyrl_tpu.envs import make_env  # noqa: E402

geese_ref = harness.load_module(os.path.join(BENCH, "reference", "geesenet.py"))
xfmr_ref = harness.load_module(os.path.join(BENCH, "reference", "xfmr_d1536.py"))
geese_flops = harness.load_module(os.path.join(BENCH, "flops", "geese_conv.py"))
xfmr_flops = harness.load_module(os.path.join(BENCH, "flops", "alibi_transformer.py"))

TINY_NET = {"d_model": 32, "n_heads": 2, "n_layers": 2, "memory_len": 4}


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _env(env_args, **train_args):
    cfg = normalize_args({"env_args": env_args, "train_args": train_args})
    return make_env(cfg["env_args"]), dict(cfg["train_args"], env=cfg["env_args"])


def test_geesenet_reference_matches_the_module_at_published_size():
    random.seed(0)
    env, _ = _env({"env": "HungryGeese"})
    module = env.net()
    assert (module.filters, module.blocks) == (32, 12)
    params = traffic.seeded_params(module, env, 5)
    # the heads were drawn, not left at the module's zero init
    assert float(jnp.abs(params["Dense_0"]["kernel"]).max()) > 0
    obs = traffic.observation_pool(env, 24)
    want = geese_ref.forward(params, obs)
    got = module.apply({"params": params}, jnp.asarray(obs), None)
    assert float(jnp.abs(want["policy"]).max()) > 0.5
    np.testing.assert_allclose(got["policy"], want["policy"], atol=2e-4)
    np.testing.assert_allclose(got["value"], want["value"], atol=2e-5)


def test_geesenet_reference_sees_a_missing_block():
    """The tolerance the cells use would fail a tower with a block left out."""
    random.seed(0)
    env, _ = _env({"env": "HungryGeese"})
    module = env.net()
    params = traffic.seeded_params(module, env, 5)
    obs = traffic.observation_pool(env, 24)
    want = geese_ref.forward(params, obs)
    cut = {k: v for k, v in params.items() if k != "ConvBlock_12"}
    got = geese_ref.forward(cut, obs)
    verdict = harness.compare_outputs(got, want, _config("geesenet")["reference_tolerance"])
    assert not verdict["ok"]


@pytest.mark.parametrize("seed", [0, 1])
def test_transformer_reference_matches_the_module_seq_mode(seed):
    env, _ = _env({"env": "Geister", "net": "transformer", "net_args": TINY_NET})
    module = env.net()
    params = traffic.seeded_params(module, env, seed)
    rng = np.random.default_rng(seed)
    n, t = 3, 12
    obs = {"board": rng.normal(size=(n, t, 7, 6, 6)).astype(np.float32),
           "scalar": rng.normal(size=(n, t, 18)).astype(np.float32)}
    observed = (rng.uniform(size=(n, t)) < 0.7).astype(np.float32)
    config = {"env_args": {"net_args": TINY_NET}}
    want = xfmr_ref.forward(params, obs, observed, config)
    got = module.apply({"params": params}, obs, None, seq=True, key_mask=jnp.asarray(observed))
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(got[head], want[head], atol=2e-5)


def test_transformer_reference_matches_stepping_through_the_ring():
    """The window form equals a player stepping with the KV ring: the
    property the module promises and the reference is written from."""
    env, _ = _env({"env": "Geister", "net": "transformer", "net_args": TINY_NET})
    module = env.net()
    params = traffic.seeded_params(module, env, 2)
    rng = np.random.default_rng(2)
    t = 10                                      # longer than memory_len 4
    obs = {"board": rng.normal(size=(1, t, 7, 6, 6)).astype(np.float32),
           "scalar": rng.normal(size=(1, t, 18)).astype(np.float32)}
    want = xfmr_ref.forward(params, obs, np.ones((1, t), np.float32),
                            {"env_args": {"net_args": TINY_NET}})
    hidden = module.initial_state((1,))
    for step in range(t):
        out = module.apply({"params": params}, jax.tree.map(lambda x: x[:, step], obs), hidden)
        hidden = out["hidden"]
        np.testing.assert_allclose(out["policy"], want["policy"][:, step], atol=2e-5)


def test_forward_rows_matches_the_train_steps_forward():
    from handyrl_tpu.parallel.train_step import forward_prediction

    random.seed(4)
    np.random.seed(4)
    env, args = _env({"env": "Geister", "net": "transformer", "net_args": TINY_NET},
                     batch_size=3, burn_in_steps=2, forward_steps=6, observation=True,
                     seq_attention="einsum")
    module = env.net()
    params = traffic.seeded_params(module, env, 4)
    batch = traffic.random_play_batches(env, module, args, 1, 3)[0]
    got = forward_prediction(module, params, batch, dict(args, _mesh=None))
    want = xfmr_ref.forward_rows(params, batch, {"env_args": {"net_args": TINY_NET}}, 2)
    legal = (batch["action_mask"][:, 2:] == 0) & (batch["turn_mask"][:, 2:] > 0)
    observed = batch["observation_mask"][:, 2:] > 0
    verdict = harness.compare_outputs(
        got, want, 1e-4, {"policy": legal, "value": observed, "return": observed})
    assert verdict["ok"], verdict


def test_geesenet_counts_against_a_hand_count():
    """One block: 77 cells x 9 taps x 32 in x 32 out = 709,632 multiply-adds.
    Stem: 77 x 9 x 17 x 32 = 376,992.  Heads: 32 x 4 + 64 x 1 = 192."""
    assert geese_flops.forward_macs(32, 1) - geese_flops.forward_macs(32, 0) == 709632
    assert geese_flops.forward_macs(32, 0) == 376992 + 192
    assert geese_flops.forward_macs(32, 12) == 8892768
    env, _ = _env({"env": "HungryGeese"})
    params = traffic.seeded_params(env.net(), env, 0)
    assert geese_flops.parameters(32, 12) == sum(x.size for x in jax.tree.leaves(params))
    cell = json.load(open(os.path.join(BENCH, "workloads", "geese_loop.json")))
    work = geese_flops.train_update(_config("geesenet"), cell)
    # B128 x T16 observations, forward + two backward passes, 2 FLOP a MAC
    assert work["observations"] == 2048
    assert work["flops"] == 6 * 8892768 * 2048


def test_transformer_counts_against_a_hand_count():
    """One block at d 1536, per token: q, k, v, o 4 d^2 + MLP 8 d^2 =
    12 x 2,359,296 = 28,311,552, and 2 x 32 keys x 1536 = 98,304 for the
    scores and the weighted sum over the ring's 32 steps."""
    d = 1536
    one = (xfmr_flops.forward_macs_per_token(270, d, 1, 4, 32, 214, 2)
           - xfmr_flops.forward_macs_per_token(270, d, 0, 4, 32, 214, 2))
    assert one == 28311552 + 98304
    config = _config("xfmr_d1536")
    env, _ = _env(config["env_args"])
    module = env.net()
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), obs, module.initial_state((1,))))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert xfmr_flops.parameters(270, d, 8, 4, 214, 2) == n_params
    assert round(n_params / 1e6, 1) == 229.8
    cell = json.load(open(os.path.join(BENCH, "workloads", "xfmr_train_t64.json")))
    work = xfmr_flops.train_update(config, cell)
    assert work["tokens"] == 64 * 2 * 64
    # close to the rule of thumb 6 x parameters x tokens (11.3 TFLOP)
    assert 0.95 < work["flops"] / (6 * n_params * work["tokens"]) < 1.02
