"""``traffic.balance_routers``: a routed net's selection biases at the
balancing rule's fixed point, made from the seed and nothing else.

On a toy router in plain ``jax.numpy`` (``routed_toy.py``'s scores and top-k
under a ``score_bias``, tokens with a common part so that the seeded router is
several times out of balance), and on the tiny ``zaya1_8b`` net of
``tiny_zaya/`` through the program's own forward, where the rows
``window_loads`` counts are held to the rows the net itself counts.

Collected through ``test_reference.py``'s last line (a name of its own that pytest
does not collect by: a shim of its own in ``tests/`` is owed):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_reference.py -q -k "bias or held or routed or judged or window_loads or patience"
"""

import json
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import routed_toy  # noqa: E402
from benchmark import traffic  # noqa: E402

# what a file that collects these cases for another directory takes (``import *``)
__all__ = [
    "test_the_held_experts_end_within_a_tenth_of_their_share",
    "test_one_seed_twice_is_one_bias_to_the_bit_and_two_seeds_are_two",
    "test_a_bias_stays_a_value_of_its_leaf_s_dtype",
    "test_a_net_with_no_routed_layer_comes_back_untouched",
    "test_a_round_that_cannot_settle_ends_by_patience_with_its_best",
    "test_judged_and_timed_parameters_are_one_tree",
    "test_window_loads_counts_the_rows_the_net_counts",
    "test_the_tiny_net_s_held_experts_come_nearer_their_share",
    "tiny_zaya_net",
]

LAYERS, EXPERTS, TOP_K = ("layer0", "layer1"), 8, 2
HELD = slice(2, 6)


def _toy(seed, dtype=jnp.float32):
    """(params, batches): two routed layers' routers and zero biases in the
    program's layout, and four batches of tokens that share a direction."""
    params, batches = {}, []
    for i, layer in enumerate(LAYERS):
        made, _ = routed_toy.make(seed + 101 * i, width=16, experts=EXPERTS)
        params[layer] = {"mixer": {"router": 2.0 * made["router"],
                                   traffic.ROUTER_BIAS: jnp.zeros((EXPERTS,), dtype)}}
    for b in range(4):
        _, batch = routed_toy.make(seed + 7 * b, tokens=(16, 8, 2), width=16)
        batches.append({"x": batch["x"] + 1.5 * batch["x"][0, 0, 0]})
    return params, batches


@jax.jit
def _toy_loads(params, batch):
    """Each layer's rows an expert: layer1 reads the tokens layer0's choice moved."""
    loads, x = {}, batch["x"]
    for layer in LAYERS:
        mixer = params[layer]["mixer"]
        scores = routed_toy._scores(mixer, x)
        top = routed_toy._top(scores + mixer[traffic.ROUTER_BIAS].astype(scores.dtype), TOP_K)
        picked = jax.nn.one_hot(top, EXPERTS).sum(axis=-2)
        loads[layer] = picked.sum(axis=(0, 1, 2))
        x = x + 0.5 * (picked @ mixer["router"].T)
    return loads


def _held_over_share(params, batches):
    total = None
    for batch in batches:
        got = _toy_loads(params, batch)
        total = got if total is None else jax.tree.map(jnp.add, total, got)
    return {k: np.asarray(v)[HELD] / np.asarray(v).mean() for k, v in total.items()}


@pytest.mark.parametrize("seed", [3, 2971215073])
def test_the_held_experts_end_within_a_tenth_of_their_share(seed):
    params, batches = _toy(seed)
    before = _held_over_share(params, batches)
    assert max(v.max() for v in before.values()) > 1.5, before      # the seeded router is out
    biases, note = traffic.balance_routers(params, _toy_loads, batches, HELD)
    after = _held_over_share(traffic.with_router_biases(params, biases), batches)
    assert all(np.abs(v - 1.0).max() <= 0.1 for v in after.values()), after
    assert note["settled"] and 0 < note["rounds"] <= 200
    assert note["layers"] == len(LAYERS) and note["worst_off_share"] <= 0.1
    # the seeded router's reading is the first round's, on the quarter of the batches it runs
    assert note["seeded_load_over_share"] == max(
        v.max() for v in _held_over_share(params, batches[:1]).values())
    assert note["load_over_share"] == max(v.max() for v in after.values())


def test_one_seed_twice_is_one_bias_to_the_bit_and_two_seeds_are_two():
    def drawn(seed):
        params, batches = _toy(seed)
        return traffic.balance_routers(params, _toy_loads, batches, HELD)[0]

    first, again, other = drawn(5), drawn(5), drawn(6)
    assert traffic.same_biases(first, again)
    assert all(v.tobytes() == again[k].tobytes() for k, v in first.items())
    assert not traffic.same_biases(first, other)


def test_a_bias_stays_a_value_of_its_leaf_s_dtype():
    params, batches = _toy(3, jnp.bfloat16)
    biases, _ = traffic.balance_routers(params, _toy_loads, batches, HELD)
    for bias in biases.values():
        assert bias.dtype == np.float32 and np.any(bias != 0)
        assert np.array_equal(bias, bias.astype(jnp.bfloat16).astype(np.float32))


def test_a_net_with_no_routed_layer_comes_back_untouched():
    params = {"enc": {"kernel": jnp.ones((4, 4))}, "head": {"bias": jnp.zeros((4,))}}

    def never(params, batch):
        raise AssertionError("no forward pass is owed to a net with no routed layer")

    biases, note = traffic.balance_routers(params, never, [{}], HELD)
    assert biases == {} and note == {"layers": 0, "rounds": 0}
    same = traffic.with_router_biases(params, biases)
    assert jax.tree.structure(same) == jax.tree.structure(params)
    assert all(a is b for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(params)))


def test_a_round_that_cannot_settle_ends_by_patience_with_its_best(monkeypatch):
    monkeypatch.setattr(traffic, "BALANCE_WITHIN", -1.0)    # no round can reach it
    params, batches = _toy(3)
    biases, note = traffic.balance_routers(params, _toy_loads, batches, HELD)
    assert not note["settled"]
    assert note["rounds"] - note["best_round"] == traffic.BALANCE_PATIENCE
    after = _held_over_share(traffic.with_router_biases(params, biases), batches)
    assert max(np.abs(v - 1.0).max() for v in after.values()) == note["worst_off_share"]


@pytest.mark.parametrize("fault", ["none", "a_bias_moved", "a_layer_missing"])
def test_judged_and_timed_parameters_are_one_tree(fault):
    params, batches = _toy(9)
    biases, _ = traffic.balance_routers(params, _toy_loads, batches, HELD)
    timed = traffic.with_router_biases(params, biases)
    judged = traffic.with_router_biases(_toy(9)[0], biases)     # drawn a second time
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(timed),
                                                    jax.tree.leaves(judged)))
    theirs = traffic.router_biases(judged)
    if fault == "a_bias_moved":
        theirs[LAYERS[1]] = np.nextafter(theirs[LAYERS[1]], np.float32(1))
    if fault == "a_layer_missing":
        del theirs[LAYERS[0]]
    assert traffic.same_biases(traffic.router_biases(timed), theirs) == (fault == "none")


# -- the program's own net -------------------------------------------------

@pytest.fixture(scope="module")
def tiny_zaya_net():
    """The tiny zaya cell's net, seeded weights, step arguments and staged batches."""
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh

    root = os.path.join(HERE, "tiny_zaya")
    with open(os.path.join(root, "configs", "tiny_zaya.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "workloads", "tiny_zaya_train.json")) as f:
        cell = json.load(f)
    cfg = normalize_args({
        "env_args": dict(config["env_args"]),
        "train_args": dict(config.get("train_args", {}), **cell["train_args"], seed=11),
    })
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(11)
    np.random.seed(11)
    env = make_env(args["env"])
    module = env.net()
    params = traffic.seeded_params(module, env, 11)
    ctx = TrainContext(module, args, make_mesh(cell["mesh"], devices=jax.devices()[:1]))
    batches = [ctx.put_batch(b) for b in traffic.random_play_batches(
        env, module, args, cell["n_batches"], cell["fill_episodes"])]
    return module, ctx, params, batches


def test_window_loads_counts_the_rows_the_net_counts(tiny_zaya_net):
    from handyrl_tpu.parallel.train_step import forward_prediction

    module, ctx, params, batches = tiny_zaya_net
    cast = traffic.in_compute_dtype(params, ctx.args["compute_dtype"])
    loads = jax.device_get(traffic.window_loads(module, ctx.args)(cast, batches[0]))
    assert sorted(loads) == sorted(traffic.router_biases(params))
    held = slice(module.expert_offset, module.expert_offset + module.experts_held)
    counters = jax.device_get(jax.jit(
        lambda p, b: forward_prediction(module, p, b, ctx.args)["counters"])(cast, batches[0]))
    assert sum(v[held].sum() for v in loads.values()) == counters["rows_held"] > 0
    assert max(v[held].max() for v in loads.values()) == counters["expert_rows_max"]
    tokens = float((np.asarray(batches[0]["observation_mask"]) > 0).sum())
    assert all(v.sum() == tokens * module.top_k for v in loads.values())


def test_the_tiny_net_s_held_experts_come_nearer_their_share(tiny_zaya_net):
    module, ctx, params, batches = tiny_zaya_net
    held = slice(module.expert_offset, module.expert_offset + module.experts_held)
    biases, note = traffic.balance_routers(
        traffic.in_compute_dtype(params, ctx.args["compute_dtype"]),
        traffic.window_loads(module, ctx.args), batches, held)
    assert note["layers"] == 3 and note["rounds"] > 0
    # a few dozen tokens an expert: it cannot promise a tenth, and it says what it reached
    assert note["load_over_share"] < note["seeded_load_over_share"]
    balanced = traffic.with_router_biases(params, biases)
    assert traffic.same_biases(traffic.router_biases(balanced), biases)
    moved = [k for k, (a, b) in enumerate(zip(jax.tree.leaves(params),
                                              jax.tree.leaves(balanced))) if a is not b]
    assert len(moved) == note["layers"]         # the biases and no other leaf
