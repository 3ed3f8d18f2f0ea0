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


# -- a routed forward against its reference: harness.judge_forward -------------
# Four experts, top-2, plain jax.numpy (routed_toy.py): the system mixes the
# experts through a one-hot mask, the reference gathers the chosen.

sys.path.insert(0, HERE)
import routed_toy as toy  # noqa: E402

TOY = {"name": "toy", "top_k": 2, "reference_tolerance": 1e-4, "choices_agreement_floor": 0.9}
TOY_TOKENS = 2 * 5 * 2          # rows x steps past the burn-in x players


def _toy(seed=0):
    """Seeded rows with a near-tie injected at the first compared token: it
    is the first unit vector, so the router's first row is its logits.
    Expert 0 leads; 1 and 2 lie 2e-6 apart for second place; 3 is far off."""
    params, batch = toy.make(seed)
    x = batch["x"].at[0, 1, 0].set(jnp.eye(8)[0])
    router = params["router"].at[0].set(jnp.asarray([3.0, 1.0, 1.0 - 2e-6, -3.0]))
    return dict(params, router=router), {"x": x}


def _judge(system, config=TOY, reference=toy.reference_rows, **kwargs):
    params, batch = _toy()
    return harness.judge_forward(
        lambda p, b: system(p, b, config, 1), reference, params, batch, config, 1, **kwargs)


def test_routed_system_matches_when_nothing_is_wrong():
    checks, notes, compared = _judge(toy.system_rows)
    assert checks == {"matches_reference": True, "choices_agree": True}
    assert notes["choices_agreement"] == 1.0 and notes["reference_free_max_abs_diff"]["ok"]
    assert compared["choices_agreement"] == [1.0, 0.9]
    number, limit = compared["policy"]
    assert number < 1e-5 and limit == pytest.approx(1e-4 * notes["reference_max_abs_diff"]["policy_scale"])


def test_a_near_tie_fails_the_free_comparison_and_passes_the_forced_one():
    """What rounding does to a top-k: the system's scores differ by 4e-6 in
    one column, one token takes expert 2 where the reference takes 1, and a
    whole expert's contribution stands between the two outputs.  Forced to
    the system's choice the reference agrees to the tolerance."""
    def rounded(params, batch, config, burn_in):
        router = params["router"].at[0, 2].add(4e-6)
        return toy.system_rows(dict(params, router=router), batch, config, burn_in)

    checks, notes, _ = _judge(rounded)
    assert notes["choices_agreement"] == pytest.approx((TOY_TOKENS - 1) / TOY_TOKENS)
    assert notes["reference_free_max_abs_diff"]["ok"] is False
    assert notes["reference_free_max_abs_diff"]["policy"] > 100 * TOY["reference_tolerance"]
    assert checks == {"matches_reference": True, "choices_agree": True}


def test_an_expert_perturbed_by_a_hundredth_fails_the_forced_comparison():
    def perturbed(params, batch, config, burn_in):
        experts = params["experts"].at[1].multiply(1.01)
        return toy.system_rows(dict(params, experts=experts), batch, config, burn_in)

    checks, notes, compared = _judge(perturbed)
    assert checks == {"matches_reference": False, "choices_agree": True}
    assert compared["policy"][0] > compared["policy"][1]


def test_scores_from_the_wrong_column_pass_the_forced_comparison_and_fail_choices_agree():
    """The forced comparison hides no routing fault: a top-k taken from the
    neighbouring column's scores computes every chosen expert rightly, and
    chooses wrongly."""
    def misrouted(params, batch, config, burn_in):
        return toy.system_rows(params, batch, config, burn_in,
                               select=lambda s: jnp.roll(s, 1, axis=-1))

    checks, notes, compared = _judge(misrouted)
    assert checks == {"matches_reference": True, "choices_agree": False}
    assert compared["choices_agreement"][0] == notes["choices_agreement"] < 0.5


def test_one_wrong_expert_fails_the_float32_comparison():
    config = dict(TOY, reference_tolerance_f32=1e-5)

    def one_wrong(params, batch):
        def select(scores):         # the first compared token takes its worst expert
            worst = jax.nn.one_hot(jnp.argmin(scores[0, 0, 0]), 4)
            return scores.at[0, 0, 0].add(10.0 * worst)
        return toy.system_rows(params, batch, config, 1, select=select)

    sound = lambda p, b: toy.system_rows(p, b, config, 1)  # noqa: E731
    checks, notes, compared = _judge(toy.system_rows, config, system_f32=sound)
    assert checks == {"matches_reference": True, "choices_agree": True,
                      "matches_reference_f32": True}
    assert compared["f32_policy"][0] <= compared["f32_policy"][1]
    checks, notes, compared = _judge(toy.system_rows, config, system_f32=one_wrong)
    assert checks == {"matches_reference": True, "choices_agree": True,
                      "matches_reference_f32": False}
    assert "reference_f32_max_abs_diff" in notes
    with pytest.raises(ValueError, match="reference_tolerance_f32"):
        _judge(toy.system_rows, config)


def _plain_reference(params, batch, config, burn_in):
    return {"policy": toy.reference_rows(params, batch, config, burn_in)["policy"]}


def _plain_system(params, batch, config, burn_in):
    return {"policy": toy.system_rows(params, batch, config, burn_in)["policy"]}


def test_choices_on_one_side_only_is_an_error():
    with pytest.raises(ValueError, match="returned choices, and the reference"):
        _judge(toy.system_rows, reference=_plain_reference)
    with pytest.raises(ValueError, match="the reference takes choices"):
        _judge(_plain_system)

    def deaf(params, batch, config, burn_in, choices=None):
        return toy.reference_rows(params, batch, config, burn_in)

    def rounded(params, batch, config, burn_in):
        router = params["router"].at[0, 2].add(4e-6)
        return toy.system_rows(dict(params, router=router), batch, config, burn_in)

    with pytest.raises(ValueError, match="did not use the choices"):
        _judge(rounded, reference=deaf)


def test_a_configuration_without_choices_gives_todays_verdict_key_for_key():
    """What runners/train_step.py did before the comparison moved here."""
    params, batch = _toy()
    mask = np.arange(5)[None, None, None, :] > 0        # one logit never counts
    with jax.default_matmul_precision("highest"):
        want = jax.device_get(jax.jit(
            lambda p, b: _plain_reference(p, b, TOY, 1))(params, batch))
    got = jax.device_get(jax.jit(lambda p, b: _plain_system(p, b, TOY, 1))(params, batch))
    today = harness.compare_outputs(got, want, TOY["reference_tolerance"], {"policy": mask})
    checks, notes, compared = _judge(_plain_system, reference=_plain_reference,
                                     mask_of=lambda head: mask)
    assert checks == {"matches_reference": today.pop("ok")} == {"matches_reference": True}
    assert notes == {"reference_max_abs_diff": today}
    assert list(today) == ["policy", "policy_scale"]
    assert compared == {"policy": [today["policy"], 1e-4 * max(1.0, today["policy_scale"])]}


def test_choices_agreement_counts_sets_over_the_tokens_that_count():
    ours = {"a": np.asarray([[[0, 1], [2, 3]]]), "b": np.asarray([[[1, 0], [1, 2]]])}
    theirs = {"a": np.asarray([[[1, 0], [2, 1]]]), "b": np.asarray([[[0, 1], [2, 1]]])}
    assert harness.choices_agreement(ours, theirs) == 0.75          # order does not count
    assert harness.choices_agreement(ours, theirs, np.asarray([[[1], [0]]])) == 1.0
    with pytest.raises(ValueError, match="routed layer"):
        harness.choices_agreement(ours, {"a": theirs["a"]})
    with pytest.raises(ValueError, match="float32"):
        harness.choices_agreement({"a": np.zeros((1, 2, 2), np.float32)}, {"a": theirs["a"]})


# ``traffic.balance_routers``' cases (``balanced_router_cases.py``: a name pytest
# does not collect by, so each case runs once, wherever this file is collected;
# a shim of their own in ``tests/`` is owed by a PR that may add one: PERF.md)
from balanced_router_cases import *  # noqa: E402,F401,F403  (HERE is on sys.path: routed_toy above)
