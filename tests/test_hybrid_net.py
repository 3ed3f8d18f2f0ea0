"""HybridNet (models/hybrid.py: Mamba-2 mixers, routed experts with a shared
one, grouped-query attention, by a layer-pattern string) at tiny widths on
the CPU, against the plain reference of the configuration it was written
for (benchmark/reference/nemotron_twotower_30b_a3b.py, which imports
nothing from handyrl_tpu.models): window mode per mixer and whole, and whole
Geister windows through ``forward_prediction`` against the scan path.

What was cut from this file (PR 67) is beside it: the packed windows in
test_hybrid_packed.py, the train step, the refusals and the count of its work
in test_hybrid_step.py, the routed experts' kernels against loops in
test_routed_experts.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.ops.ssd import ssd_chunked, ssd_step
from handyrl_tpu.parallel.train_step import forward_prediction
from nets import (HYBRID, SCAN, _geister_windows, _module, _params, _predict, _random_window, _reference,
                  _window)

REFERENCE = HYBRID.REFERENCE


# -- the system against the plain reference, float32 ----------------------


def _biased(params, scale, seed=0):
    """``params`` with every routed layer's choosing bias drawn at ``scale``."""
    rng, out = np.random.RandomState(seed), dict(params)
    for name in [n for n in params if n.startswith("layer") and "score_bias" in params[n]["mixer"]]:
        bias = scale * rng.randn(*params[name]["mixer"]["score_bias"].shape)
        out[name] = dict(params[name], mixer=dict(
            params[name]["mixer"], score_bias=jnp.asarray(bias, jnp.float32)))
    return out


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEM*E", "MEMEM*EME"])
def test_window_matches_the_reference_per_mixer_and_whole(pattern):
    module = _module(HYBRID, pattern=pattern)
    obs, mask = _random_window(1)
    params = _biased(_params(module, obs), 0.2)
    got = _window(module, params, obs, mask)
    want = _reference(HYBRID, params, obs, mask, pattern=pattern)
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(
            np.asarray(got[head]) * np.asarray(mask)[..., None],
            np.asarray(want[head]) * np.asarray(mask)[..., None], atol=2e-5)
    if "E" in pattern:
        for layer, chosen in want["choices"].items():
            assert np.array_equal(np.sort(np.asarray(got["choices"][layer]), -1),
                                  np.sort(np.asarray(chosen), -1))


@pytest.mark.parametrize("steps", [6, 12])
def test_a_window_of_one_and_a_half_and_of_three_chunks(steps):
    """The state is passed between chunks of 4: 6 steps are 1.5 chunks, 12 are 3."""
    module = _module(HYBRID, pattern="MM")
    obs, _ = _random_window(2, steps=steps)
    mask = jnp.ones((3, steps))
    params = _params(module, obs)
    got = _window(module, params, obs, mask)
    want = _reference(HYBRID, params, obs, mask, pattern="MM")
    np.testing.assert_allclose(got["policy"], want["policy"], atol=2e-5)


def test_chunked_scan_is_the_recurrence():
    rng = np.random.RandomState(0)
    n, length, h, p, g, s = 2, 11, 4, 8, 2, 6
    x = jnp.asarray(rng.randn(n, length, h, p), jnp.float32)
    B, C = (jnp.asarray(rng.randn(n, length, g, s), jnp.float32) for _ in range(2))
    dt = jnp.asarray(rng.rand(n, length, h) * (rng.rand(n, length, 1) > 0.3), jnp.float32)
    A = -jnp.asarray(rng.rand(h) * 4 + 0.5, jnp.float32)
    state = jnp.asarray(rng.randn(n, h, p, s), jnp.float32)
    y, last = ssd_chunked(x, dt, A, B, C, state, 4)
    want = []
    for t in range(length):
        y_t, state = ssd_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], state)
        want.append(y_t)
    np.testing.assert_allclose(y, jnp.stack(want, axis=1), atol=1e-4)
    np.testing.assert_allclose(last, state, atol=1e-4)


# -- whole window against step mode, through forward_prediction ------------


@pytest.fixture(scope="module")
def geister():
    return _geister_windows(HYBRID, batch_size=3, burn_in_steps=3, forward_steps=9)


def test_whole_window_matches_the_scan_path_with_unobserved_steps_and_burn_in(geister):
    _, args, module, params, batch = geister
    window = _predict(module, args)(params, batch)
    scan = _predict(module, args, **SCAN)(params, batch)
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(window[head], scan[head], atol=2e-5)
    assert "choices" not in scan and set(window["choices"]) == {"forward", "window_start"}
    chosen = window["choices"]["forward"]
    assert set(chosen) == {"layer1", "layer4"} and chosen["layer1"].shape == (3, 9, 2, 2)
    assert chosen["layer1"].dtype == jnp.int32


def test_a_remat_rung_the_net_lacks_is_refused_by_name():
    obs, mask = _random_window(0)
    module = _module(HYBRID)
    params = _params(module, obs)
    with pytest.raises(ValueError, match=r"HybridNet: remat='attn' not one of"):
        module.apply({"params": params}, obs, None, seq=True, key_mask=mask, remat="attn")


def test_burn_in_stops_gradients_through_every_carried_state(geister):
    """The scan path's burn-in rule: what the burn-in steps leave carries no
    gradient, so the window path's parameter gradient equals the scan's."""
    _, args, module, params, batch = geister

    def loss(p, scan):
        out = forward_prediction(module, p, batch, dict(args, **(SCAN if scan else {})))
        return sum(jnp.sum(jnp.square(jnp.where(jnp.abs(out[k]) < 1e6, out[k], 0.0)))
                   for k in ("policy", "value", "return"))

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    window, scan = grad(params, False), grad(params, True)
    for a, b in zip(jax.tree.leaves(window), jax.tree.leaves(scan)):
        np.testing.assert_allclose(a, b, atol=5e-4 * max(1.0, float(jnp.abs(b).max())))


def test_the_three_comparisons_hold_and_the_faults_fail(geister):
    """``harness.judge_forward`` on the system as it is, then with an 8-bit
    forward, a dropped layer and a router that reads the wrong column."""
    from benchmark import harness

    config, args, module, params, batch = geister
    config = dict(config, reference_tolerance=1e-4, choices_agreement_floor=0.99,
                  reference_tolerance_f32=1e-4)
    burn_in = args["burn_in_steps"]
    legal = (batch["action_mask"][:, burn_in:] == 0) & (batch["turn_mask"][:, burn_in:] > 0)
    observed = batch["observation_mask"][:, burn_in:] > 0

    def judge(system):
        checks, _, compared = harness.judge_forward(
            system, REFERENCE.forward_rows, params, batch, config, burn_in,
            mask_of=lambda head: legal if head == "policy" else observed, system_f32=system)
        return checks, compared

    sound = _predict(module, args)
    checks, compared = judge(sound)
    assert all(checks.values()) and set(checks) == {
        "matches_reference", "choices_agree", "matches_reference_f32"}, (checks, compared)

    def eight_bits(p, b):
        p = jax.tree.map(lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32), p)
        return forward_prediction(module, p, b, args)

    def dropped_layer(p, b):
        idle = jax.tree.map(jnp.zeros_like, p["layer2"]["mixer"]["out_proj"])
        return forward_prediction(module, dict(p, layer2=dict(p["layer2"], mixer=dict(
            p["layer2"]["mixer"], out_proj=idle))), b, args)

    def wrong_column(p, b):
        mixer = p["layer1"]["mixer"]
        router = jnp.roll(mixer["router"], 1, axis=1)
        return forward_prediction(module, dict(p, layer1=dict(p["layer1"], mixer=dict(
            mixer, router=router))), b, args)

    assert not judge(eight_bits)[0]["matches_reference"]
    assert not judge(dropped_layer)[0]["matches_reference"]
    checks, compared = judge(wrong_column)
    assert not checks["choices_agree"] and not checks["matches_reference_f32"], compared


def test_gradients_match_the_references(geister):
    config, args, module, params, batch = geister
    args = dict(args, burn_in_steps=0)

    def system(p):
        out = forward_prediction(module, p, batch, args)
        return sum(jnp.sum(jnp.square(out[k] * batch["observation_mask"]))
                   for k in ("value", "return"))

    def reference(p):
        out = REFERENCE.forward_rows(p, batch, config, 0)
        return sum(jnp.sum(jnp.square(out[k] * batch["observation_mask"]))
                   for k in ("value", "return"))

    got, want = jax.jit(jax.grad(system))(params), jax.jit(jax.grad(reference))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=2e-4 * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))

