"""``HybridNet`` with ``L`` layers (multi-head latent attention: a ring of
latents with one rotated key part, keys wider than values), a dense ``-``
layer and gated ``sigmoid``-routed ``E`` layers with a shared expert in one
pattern, against the plain reference of ``kanana_2_30b_a3b``
(``benchmark/reference/kanana_2_30b_a3b.py``), at a small size on the CPU:
the expanded form of a window against the absorbed form of a step, the
burn-in hand-off of latents, the acting rows' rings stepped in place, the
faults the comparison must tell, and the system's entry points.  The scan
over periods against the unrolled stack and the eight shares of the
eight-chip deployment are in tests/test_kanana_periods.py since PR 67."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import MLA_CORE_SCOPE, MLA_PROJ_SCOPE, HybridNet
from handyrl_tpu.parallel.train_step import pack_order
from handyrl_tpu.utils.compile_cache import scoped_program_options
from nets import KANANA, REPO, _apart, _load, _scan, _window

NET = KANANA.net
SLOT = NET["kv_latent"] + NET["qk_rope_dim"]        # what a ring keeps of a step
REFERENCE = KANANA.REFERENCE
_module, _reference = (functools.partial(f, KANANA) for f in (nets._module, nets._reference))
ROWS, STEPS = KANANA.rows, KANANA.steps
# float32 under "highest": the sound forward reads 2e-6 of a head's scale,
# the mildest fault below 3e-3
F32_TOLERANCE = 2e-4
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.05


@pytest.fixture(scope="module")
def toy():
    made = nets._toy(KANANA)
    mask = made[3]
    # more tokens in a row than the ring has slots: it wraps
    assert 0.3 < float(mask.mean()) < 0.8 and int(mask.sum(axis=1).max()) > NET["memory_len"]
    return made


# -- both forms against the plain reference ---------------------------------


@pytest.mark.parametrize("choices", ["free", "forced"])
def test_window_mode_is_the_reference_in_float32(toy, choices):
    """The whole window (the expanded form, scores as two products summed)
    against the reference (one concatenated key a head), its own choices and
    those the system made handed to it; the experts chosen are the same sets,
    and the dense layer and the expert layers keep their own widths."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask)
    if choices == "forced":
        want = _reference(params, obs, mask, choices=got["choices"])
    assert _apart(got, want, mask) < 2e-5
    seen = np.asarray(mask) > 0
    assert sorted(got["choices"]) == ["layer3", "layer5"]
    for name, chosen in got["choices"].items():
        assert chosen.shape == (ROWS, STEPS, 3)
        np.testing.assert_array_equal(np.sort(np.asarray(chosen)[seen], axis=-1),
                                      np.sort(np.asarray(want["choices"][name])[seen], axis=-1))
    assert len({int(e) for c in got["choices"].values() for e in np.asarray(c)[seen].ravel()}) > 4
    assert params["layer1"]["mixer"]["up"]["kernel"].shape == (32, 48)
    assert params["layer3"]["mixer"]["w1"].shape == (4, 32, 32)
    assert params["layer3"]["mixer"]["shared_up"]["kernel"].shape == (32, 48)


def test_the_absorbed_steps_are_the_expanded_window(toy):
    """Fourteen steps of step mode (the query taken into the latent, scores
    and mix against the ring as it lies, the value half after), the ring of six
    evicting, equal the window and the reference; what a layer keeps is one
    ring of ``kv_latent + qk_rope`` values a slot, and nothing per head."""
    module, params, obs, mask, want = toy
    got, hidden = _scan(module, params, obs, mask)
    assert _apart(got, want, mask) < 2e-5
    assert _apart(got, _window(module, params, obs, mask), mask) < 2e-5
    for kind, state in zip(NET["pattern"], hidden["layers"]):
        if kind != "L":
            assert not state
            continue
        assert list(state) == ["latent"] and state["latent"].shape == (ROWS, 6, SLOT)
        assert state["latent"].dtype == jnp.float32
        # a row that saw more than six tokens has every slot written
        full = np.asarray(mask.sum(axis=1)) >= 6
        assert (np.abs(np.asarray(state["latent"]))[full].max(axis=-1) > 0).all()
    per_head = NET["n_heads"] * (NET["qk_nope_dim"] + NET["qk_rope_dim"] + NET["v_head_dim"])
    assert SLOT < per_head / 4


@pytest.mark.parametrize("burn_in", [1, 4, 9])
def test_a_window_split_at_burn_in_is_the_unsplit_window(toy, burn_in):
    """The burn-in steps as a window of their own hand on their latents (not
    keys and values): the forward steps expand them with their own and read
    what the unsplit window reads, no gradient goes back through what was
    handed, and the step counts what was handed beside what heads would hold."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask, burn_in=burn_in)
    assert _apart(got, want, mask) < 2e-5
    handed = 3 * ROWS * burn_in * SLOT          # three ``L`` layers, a slot a burn-in step
    assert float(got["counters"]["latent_state_values"]) == handed
    assert float(got["counters"]["expanded_state_values"]) == handed // SLOT * 4 * (8 + 4 + 6)
    # and packed by the host, as ``put_batch`` hands a long window over: each
    # part as many slots as its rows observe at most
    seen = np.asarray(mask) > 0
    order = {"burn_in": pack_order(seen[:, :burn_in], int(seen[:, :burn_in].sum(axis=1).max())),
             "forward": pack_order(seen[:, burn_in:], int(seen[:, burn_in:].sum(axis=1).max()))}
    packed = _window(module, params, obs, mask, burn_in=burn_in, packed_order=order)
    assert _apart(packed, want, mask) < 2e-5 and float(packed["counters"]["packed_dropped"]) == 0
    assert float(packed["counters"]["packed_slots"]) < float(got["counters"]["packed_slots"])
    assert float(packed["counters"]["latent_state_values"]) <= handed

    def late(o, burn):
        out = module.apply({"params": params}, {"a": o}, None, seq=True, key_mask=mask, burn_in=burn)
        return jnp.sum(jnp.square(out["value"][:, burn_in:] * mask[:, burn_in:, None]))

    grad = jax.jit(jax.grad(late), static_argnums=1)
    through, cut = grad(obs["a"], 0)[:, :burn_in], grad(obs["a"], burn_in)[:, :burn_in]
    assert float(jnp.abs(through).max()) > 1e-6 and float(jnp.abs(cut).max()) == 0.0


def test_an_unobserved_step_changes_no_state(toy):
    """What a player did not observe is no token: another observation there
    moves no observed step's output, in either form, and under ``remat:
    block`` the window is the window."""
    module, params, obs, mask, want = toy
    other = {"a": jnp.where(mask[..., None] > 0, obs["a"], 7.0 - obs["a"])}
    assert _apart(_window(module, params, other, mask, remat="block", burn_in=4), want, mask) < 2e-5
    assert _apart(_scan(module, params, other, mask)[0], want, mask) < 2e-5


def test_rows_steps_the_acting_players_ring_in_place(toy):
    """Step mode with ``rows``: the hidden tree per (row, player), the acting
    player's ring read and written where it lies (as zeros where the row's
    game has just begun), the other player's left as it was, or zeroed where
    it begins."""
    module, params, obs, _, _ = toy
    nets._rows_stepped_in_place(module, params, obs)


def test_step_mode_sows_what_its_rings_keep(toy):
    """A caller that makes ``counters`` mutable gets, a step, the values the
    rings hold and what every head's keys and values of the same slots would."""
    module, params, obs, _, _ = toy
    _, sown = jax.jit(lambda h: module.apply(
        {"params": params}, {"a": obs["a"][:, 0]}, h, mutable=["counters"]))(
            module.initial_state((ROWS,)))
    leaves = jax.tree_util.tree_leaves_with_path(sown)      # .../<name>/<index of the call>
    kept = sum(float(v) for k, v in leaves if k[-2].key == "latent_state_values")
    whole = sum(float(v) for k, v in leaves if k[-2].key == "expanded_state_values")
    assert kept == 3 * ROWS * 6 * SLOT and whole == 3 * ROWS * 6 * 4 * 18


# -- the faults the comparison must tell ---------------------------------------


_PAIRS, REFERENCE_RMS = hybrid._rope_pairs, REFERENCE.rms_norm      # the sound ones


def _half_split_queries(x, pos, theta):
    """The queries' rotated part paired (d, d + R/2), the shared key's left (2j, 2j + 1)."""
    return (hybrid._rope(x, pos, theta) if x.ndim == 4 else _PAIRS(x, pos, theta)).astype(
        jnp.float32)


def _nope_rotated(x, nope, angle):
    """The first ``qk_rope`` dimensions of the unrotated part turned too."""
    turned = x.shape[-1] - nope
    return jnp.concatenate([REFERENCE.rope(x[..., :turned], angle), x[..., turned:nope],
                            REFERENCE.rope(x[..., nope:], angle)], axis=-1)


def _a_key_a_head(part, angle, heads):
    """Every head its own rotated key part: head h's turned h positions on."""
    return jnp.concatenate([REFERENCE.rope(part[:, :, None], angle + 0.1 * h)
                            for h in range(heads)], axis=2)


REFERENCE_FAULTS = {
    "nope_part_rotated": ("turn", _nope_rotated),
    "a_rope_key_a_head": ("shared_key", _a_key_a_head),
    "scale_of_the_nope_part_alone": (
        "score_scale", lambda net: 1.0 / jnp.sqrt(float(net["qk_nope_dim"]))),
    "latent_norm_left_out": ("rms_norm", lambda x, scale, eps: (
        x * scale if x.shape[-1] == NET["kv_latent"] else REFERENCE_RMS(x, scale, eps))),
    "gates_not_renormalised": ("gates", lambda scores, chosen, net: float(
        net["routed_scale"]) * jnp.take_along_axis(scores, chosen, axis=-1)),
}
@pytest.mark.parametrize("fault", sorted(REFERENCE_FAULTS) + ["half_split_on_one_side"])
def test_a_layer_with_one_thing_wrong_fails_the_comparison(toy, fault, monkeypatch):
    """Each fault, in the reference's equations or in the system's lines,
    reads over the float32 limit that the sound pair is a hundred times under,
    with the choices forced to the system's as ``correct`` does it."""
    module, params, obs, mask, _ = toy
    in_the_system = fault == "half_split_on_one_side"
    if in_the_system:
        monkeypatch.setattr(hybrid, "_rope_pairs", _half_split_queries)
    else:
        monkeypatch.setattr(REFERENCE, *REFERENCE_FAULTS[fault])
    # the patched side is traced anew, under the patch
    got = _window(module, params, obs, mask, fresh=in_the_system)
    want = _reference(params, obs, mask, choices=got["choices"], fresh=not in_the_system)
    assert _apart(got, want, mask) > 5 * F32_TOLERANCE


def test_the_eight_bit_control_fails_where_bfloat16_holds(toy):
    """bfloat16 weights and stream hold to the reference forced to their
    choices; weights rounded leaf by leaf to float8 e4m3 first do not."""
    module, _, obs, mask, _ = toy
    sound, rough = nets._eight_bit_readings(KANANA, module, obs, mask)
    assert max(sound) < BF16_TOLERANCE < min(rough), (sound, rough)


# -- the system's entry points --------------------------------------------------


@pytest.fixture(scope="module")
def geister():
    return nets._geister_windows(KANANA, batch_size=3, burn_in_steps=4, forward_steps=12)


def test_the_scan_path_and_the_window_path_are_the_reference_on_geister(geister):
    """``forward_prediction`` through ``env.net()``: the whole-window call and
    the train step's scan over step mode, burn-in 4, against ``forward_rows``."""
    nets._both_paths_on_geister(KANANA, geister)


def test_a_train_step_moves_every_new_part_and_a_checkpoint_brings_it_back(geister, tmp_path):
    """One ``TrainContext`` update under ``remat: block``: finite, the four
    projections, the latent's norm, the dense layer, every router, the shared
    expert and the experts move, the step counts its latents; the state saved
    and loaded is the state; the layout says what the kind added; and the
    step's cache key knows the new scopes."""
    _, _, module, params, _ = geister
    metrics, moved, records = nets._update_and_checkpoint(geister, tmp_path)
    assert metrics["counter_rows_held"] > 0
    assert metrics["counter_latent_state_values"] > 0
    assert metrics["counter_latent_state_values"] / metrics["counter_expanded_state_values"] == (
        pytest.approx(SLOT / (4 * 18)))
    for path in (("layer0", "mixer", "q", "kernel"), ("layer0", "mixer", "kv_a", "kernel"),
                 ("layer2", "mixer", "kv_norm"), ("layer2", "mixer", "kv_b"),
                 ("layer4", "mixer", "o", "kernel"), ("layer1", "mixer", "gate", "kernel"),
                 ("layer3", "mixer", "router"), ("layer5", "mixer", "shared_up", "kernel"),
                 ("layer3", "mixer", "w1"), ("layer5", "mixer", "w2")):
        assert moved(*path), path
    assert not moved("layer3", "mixer", "score_bias")
    layout, = [r["attrs"] for r in records if r["name"] == "model.layout"]
    size = lambda *names: sum(  # noqa: E731
        x.size for name in names for x in jax.tree.leaves(params[name]))
    assert layout["params_latent"] == size("layer0", "layer2", "layer4")
    assert layout["params_mlp"] == size("layer1") and layout["params_experts"] == size(
        "layer3", "layer5")
    assert layout["params_attention"] == layout["params_cca"] == 0
    assert module.program_scopes() == (MLA_PROJ_SCOPE, MLA_CORE_SCOPE)
    assert _module(pattern="M*E").program_scopes() == ()
    assert scoped_program_options("opt_update", *module.program_scopes()) != (
        scoped_program_options("opt_update"))


def test_what_the_net_refuses_it_refuses_by_name():
    obs = {"a": jnp.ones((2, 5))}
    for net, said in ((dict(pattern="L-", loops=2), "a latent attention layer is run once"),
                      (dict(rope_theta=0.0), "needs rope_theta")):
        module = _module(**net)
        with pytest.raises(ValueError, match=said):
            module.init(jax.random.PRNGKey(0), obs, module.initial_state((2,)))


def test_the_published_layer_holds_what_the_issue_counted():
    """At the published widths, from shapes alone: an ``L`` sub-layer 26.35M
    (12.58 + 1.18 + 4.19 + 8.39), the dense MLP 37.75M, an expert layer's
    router 0.26M, shared expert 9.44M and 16 held experts 75.50M; the trunk
    510.3M, and ``flops/kanana.py`` counts the same net; a ring slot is 576
    values where 32 heads' keys and values would be 10,240."""
    with open(os.path.join(REPO, "benchmark", "configs", "kanana_2_30b_a3b.json")) as f:
        config = json.load(f)
    net = config["env_args"]["net_args"]
    module = HybridNet(num_actions=214, with_return=True, **net)
    layout = module.layout()
    d = 2048
    assert layout["params_latent"] // 5 - d == 12_582_912 + 1_179_648 + 512 + 4_194_304 + 8_388_608
    assert layout["params_mlp"] - d == 37_748_736
    assert layout["params_router"] // 4 == 262_144 + 128
    assert layout["params_experts"] // 4 - d - 262_272 == 9_437_184 + 75_497_472
    trunk = layout["params_latent"] + layout["params_mlp"] + layout["params_experts"]
    assert 510.2e6 < trunk < 510.4e6
    flops = _load("flops", "kanana.py")
    shapes = jax.eval_shape(lambda key: module.init(
        key, {"a": jnp.ones((1, 270))}, module.initial_state((1,)))["params"], jax.random.PRNGKey(0))
    assert flops.parameters(net, 270, 214, 2) == sum(x.size for x in jax.tree.leaves(shapes))
    state = jax.eval_shape(lambda: module.initial_state((1,)))["layers"]
    assert state[0] == {"latent": jax.ShapeDtypeStruct((1, 200, 576), jnp.float32)}
    assert not state[1] and not state[3]
    assert 32 * (192 + 128) == 10_240
