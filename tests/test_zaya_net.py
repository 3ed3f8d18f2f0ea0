"""``HybridNet`` with ``C`` layers (compressed convolutional attention) and the
``mlp`` router (top-1 gates that are not renormalised, a carry from one ``E``
layer's router to the next, no shared expert) against the plain reference of
``zaya1_8b`` (``benchmark/reference/zaya1_8b.py``), at a small size on the
CPU: both modes, the burn-in hand-off, the acting rows stepped in place, the
gradient a top-1 gate gives its router, the carry across ``remat: block``, the
faults the comparison must tell, and the system's entry points.  The scan over
periods that reads the stacked experts in place and the two shares of the
two-chip deployment are in tests/test_zaya_stack.py since PR 67."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import CCA_SCOPE, HybridNet
from handyrl_tpu.ops import attention_core, grouped_product
from handyrl_tpu.ops.routed_experts import choose
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import pack_order
from handyrl_tpu.utils.compile_cache import scoped_program_options
from nets import REPO, ZAYA, _apart, _scan, _window

NET = ZAYA.net
REFERENCE = ZAYA.REFERENCE
_module, _init, _reference, _geister = (functools.partial(f, ZAYA) for f in (
    nets._module, nets._init, nets._reference, nets._geister))
ROWS, STEPS = ZAYA.rows, ZAYA.steps
# float32 under "highest": the sound forward reads 4e-6 of a head's scale,
# the mildest fault below 2e-2
F32_TOLERANCE = 2e-4
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.06


@pytest.fixture(scope="module")
def toy():
    made = nets._toy(ZAYA)
    assert 0.3 < float(made[3].mean()) < 0.8
    return made


# -- both modes against the plain reference ---------------------------------


@pytest.mark.parametrize("choices", ["free", "forced"])
def test_window_mode_is_the_reference_in_float32(toy, choices):
    """The whole window against the reference, its own choices and those
    the system made handed to it; the experts chosen are the same sets, and
    more than one of them is used."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask)
    if choices == "forced":
        want = _reference(params, obs, mask, choices=got["choices"])
    assert _apart(got, want, mask) < 2e-5
    seen = np.asarray(mask) > 0
    for name, chosen in got["choices"].items():
        assert chosen.shape == (ROWS, STEPS, 1)
        np.testing.assert_array_equal(np.asarray(chosen)[seen], np.asarray(want["choices"][name])[seen])
    assert len({int(e) for c in got["choices"].values() for e in np.asarray(c)[seen].ravel()}) > 2
    gate = float(got["counters"]["router_gate_mean"])
    assert 1 / NET["n_experts"] < gate < 1


def test_step_by_step_is_the_whole_window(toy):
    """Fourteen steps of step mode over one parameter set, the ring of six
    keys evicting, equal the window and the reference; the state it ends
    with holds the last two observed ``[q~; k~]`` rows and the last value."""
    module, params, obs, mask, want = toy
    got, hidden = _scan(module, params, obs, mask)
    assert _apart(got, want, mask) < 2e-5
    assert _apart(got, _window(module, params, obs, mask), mask) < 2e-5
    first = hidden["layers"][0]
    assert first["tail"].shape == (ROWS, 2, 6 * 8) and first["prev_v"].shape == (ROWS, 8)
    assert float(jnp.abs(first["tail"]).min(axis=-1).max()) > 0 and not hidden["layers"][1]


@pytest.mark.parametrize("burn_in", [1, 4, 9])
def test_a_window_split_at_burn_in_is_the_unsplit_window(toy, burn_in):
    """The burn-in steps as a window of their own hand on the tail, the last
    value and the ring: the forward steps read what the unsplit window reads,
    and no gradient goes back through what was handed."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask, burn_in=burn_in)
    assert _apart(got, want, mask) < 2e-5
    # and packed by the host, as ``put_batch`` hands a long window over: each
    # part as many slots as its rows observe at most
    seen = np.asarray(mask) > 0
    order = {"burn_in": pack_order(seen[:, :burn_in], int(seen[:, :burn_in].sum(axis=1).max())),
             "forward": pack_order(seen[:, burn_in:], int(seen[:, burn_in:].sum(axis=1).max()))}
    packed = _window(module, params, obs, mask, burn_in=burn_in, packed_order=order)
    assert _apart(packed, want, mask) < 2e-5 and float(packed["counters"]["packed_dropped"]) == 0
    assert float(packed["counters"]["packed_slots"]) < float(got["counters"]["packed_slots"])

    def late(o, burn):
        out = module.apply({"params": params}, {"a": o}, None, seq=True, key_mask=mask, burn_in=burn)
        return jnp.sum(jnp.square(out["value"][:, burn_in:] * mask[:, burn_in:, None]))

    grad = jax.jit(jax.grad(late), static_argnums=1)
    through, cut = grad(obs["a"], 0)[:, :burn_in], grad(obs["a"], burn_in)[:, :burn_in]
    assert float(jnp.abs(through).max()) > 1e-6 and float(jnp.abs(cut).max()) == 0.0


def test_an_unobserved_step_changes_no_state(toy):
    """What a player did not observe is no token: another observation there
    moves no observed step's output, in either mode."""
    module, params, obs, mask, want = toy
    other = {"a": jnp.where(mask[..., None] > 0, obs["a"], 7.0 - obs["a"])}
    assert _apart(_window(module, params, other, mask), want, mask) < 2e-5
    assert _apart(_scan(module, params, other, mask)[0], want, mask) < 2e-5


def test_rows_steps_the_acting_players_leaves_in_place(toy):
    """Step mode with ``rows``: the hidden tree per (row, player), the acting
    player's tail, last value and ring read and written where they lie (as
    zeros where the row's game has just begun), the other player's rows left
    as they were, or zeroed where it begins."""
    module, params, obs, _, _ = toy
    nets._rows_stepped_in_place(module, params, obs)


# -- the router: a top-1 gate, the carry -------------------------------------


def test_the_router_gets_a_gradient_through_a_top1_gate(toy):
    """The gate is the chosen expert's own probability: every matrix of every
    router receives a gradient from the heads.  Renormalised over the chosen,
    as the other routers' gates are, a top-1 gate is 1.0 and gives none."""
    _, _, obs, mask, _ = toy
    module = _module(experts_held=8)    # uncut: whatever a token chooses is held
    params = _init(module)

    def loss(p):
        out = module.apply({"params": p}, obs, None, seq=True, key_mask=mask)
        return jnp.sum(jnp.square(out["value"] * mask[..., None]))

    grads = jax.jit(jax.grad(loss))(params)
    for layer in ("layer1", "layer3", "layer5"):
        for name in ("router_down", "router_fc1", "router_fc2", "router_out", "router_norm"):
            assert float(jnp.abs(grads[layer]["mixer"][name]).max()) > 1e-7, (layer, name)
        assert float(jnp.abs(grads[layer]["mixer"]["score_bias"]).max()) == 0.0
    assert "carry_scale" not in params["layer1"]["mixer"]
    assert float(jnp.abs(grads["layer5"]["mixer"]["carry_scale"]).max()) > 1e-7

    scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (9, 8)), axis=-1)
    own = lambda s, **how: choose(s, jnp.zeros(8), 1, 1.0, **how)[1].sum()  # noqa: E731
    np.testing.assert_array_equal(choose(scores, jnp.zeros(8), 1, 1.0)[1], 1.0)
    np.testing.assert_allclose(choose(scores, jnp.zeros(8), 1, 1.0, renormalise=False)[1][:, 0],
                               scores.max(axis=-1))
    assert float(jnp.abs(jax.grad(own)(scores)).max()) < 1e-6
    assert float(jnp.abs(jax.grad(lambda s: own(s, renormalise=False))(scores)).max()) == 1.0


def test_the_carry_crosses_remat_block(toy):
    """The router's representation goes from one ``E`` layer to the next
    through every checkpoint: ``remat: block`` gives the gradients that
    ``remat: none`` gives, the first router's from the later layers' choices
    among them."""
    module, params, obs, mask, _ = toy

    def loss(p, remat):
        out = module.apply({"params": p}, obs, None, seq=True, key_mask=mask, burn_in=3,
                           remat=remat)
        return jnp.sum(jnp.square(out["value"] * mask[..., None]))

    grad = jax.jit(jax.grad(loss), static_argnums=1)
    plain, block = (grad(params, remat) for remat in ("none", "block"))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(block), jax.tree.leaves(plain)):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(1.0, float(jnp.abs(b).max())),
                                   err_msg=str(path))
    # without the carry the first router would hear of the later ones through x alone
    silent = jax.tree.map(lambda x: x, params)
    for layer in ("layer3", "layer5"):
        silent[layer]["mixer"]["carry_scale"] = jnp.zeros(8)
    heard, unheard = (grad(p, "block")["layer1"]["mixer"]["router_down"]
                      for p in (params, silent))
    assert float(jnp.abs(heard - unheard).max()) > 1e-6


# -- the faults the comparison must tell ---------------------------------------


def _dropped_convolution(params):
    """The second convolution looks at this step alone."""
    out = jax.tree.map(lambda x: x, params)
    for layer in ("layer0", "layer2", "layer4"):
        kernel = out[layer]["mixer"]["conv1_kernel"]
        out[layer]["mixer"]["conv1_kernel"] = kernel.at[0].set(0.0)
    return out


def _no_carry(params):
    out = jax.tree.map(lambda x: x, params)
    for layer in ("layer3", "layer5"):
        out[layer]["mixer"]["carry_scale"] = jnp.zeros(8)
    return out


def _no_temperature(params):
    out = jax.tree.map(lambda x: x, params)
    for layer in ("layer0", "layer2", "layer4"):
        out[layer]["mixer"]["temp"] = jnp.ones(2)
    return out


def _longer_filter(params):
    """A third step of the depthwise filter: its older tap twice."""
    out = jax.tree.map(lambda x: x, params)
    for layer in ("layer0", "layer2", "layer4"):
        kernel = out[layer]["mixer"]["conv0_kernel"]
        out[layer]["mixer"]["conv0_kernel"] = jnp.concatenate([kernel[:1], kernel])
    return out


FAULTS = {
    # name: (the system's net arguments, what becomes of its parameters)
    "dropped_convolution": ({}, _dropped_convolution),
    "whole_head_rotation": ({"rotary_factor": 1.0}, None),
    "no_carry": ({}, _no_carry),
    "no_temperature": ({}, _no_temperature),
    "three_steps_of_depthwise": ({"cca_time0": 3}, _longer_filter),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["unshifted_value", "renormalised_gate"])
def test_a_net_with_one_thing_left_out_fails_the_comparison(toy, fault, monkeypatch):
    """Each fault reads over the float32 limit that the sound net is 50 times
    under, with the choices forced to the system's as ``correct`` does it."""
    module, params, obs, mask, _ = toy
    faulty, theirs = module, params
    if fault == "unshifted_value":      # both value halves the current token's
        real = REFERENCE.back
        monkeypatch.setattr(REFERENCE, "back", lambda x, observed, pos, j: (
            x if x.shape[-1] == 8 else real(x, observed, pos, j)))
    elif fault == "renormalised_gate":  # a top-1 gate of 1.0, as the other routers' ``choose`` makes it
        monkeypatch.setattr(hybrid, "choose", lambda *a, **how: choose(*a))
    else:
        net, change = FAULTS[fault]
        faulty, theirs = _module(**net), change(params) if change else params
    # a patched side is traced anew, under the patch
    got = _window(faulty, theirs, obs, mask, fresh=fault == "renormalised_gate")
    want = _reference(params, obs, mask, choices=got["choices"], fresh=fault == "unshifted_value")
    assert _apart(got, want, mask) > 5 * F32_TOLERANCE


def test_the_eight_bit_control_fails_where_bfloat16_holds(toy):
    """bfloat16 weights and stream hold to the reference forced to their
    choices; weights rounded leaf by leaf to float8 e4m3 first do not."""
    module, _, obs, mask, _ = toy
    sound, rough = nets._eight_bit_readings(ZAYA, module, obs, mask)
    assert max(sound) < BF16_TOLERANCE < min(rough), (sound, rough)


# -- the system's entry points --------------------------------------------------


@pytest.fixture(scope="module")
def geister():
    return nets._geister_windows(ZAYA, batch_size=3, burn_in_steps=4, forward_steps=12)


def test_the_scan_path_and_the_window_path_are_the_reference_on_geister(geister):
    """``forward_prediction`` through ``env.net()``: the whole-window call and
    the train step's scan over step mode, burn-in 4, against ``forward_rows``."""
    nets._both_paths_on_geister(ZAYA, geister)


def test_a_train_step_moves_every_new_part_and_a_checkpoint_brings_it_back(geister, tmp_path):
    """One ``TrainContext`` update under ``remat: block``: finite, the convolutions, the temperature, every router and the
    experts move, the step counts its gates; the state saved and loaded is
    the state; the layout says what the family added; and the step's cache
    key knows the new scope."""
    _, _, module, params, _ = geister
    # the process's record of attention paths: what a file before this one chose is not this step's
    metrics, moved, records = nets._update_and_checkpoint(
        geister, tmp_path, clear=(attention_core.PATHS,))
    assert metrics["counter_rows_held"] > 0 and metrics["counter_expert_passes"] == 0
    assert 1 / 8 < metrics["counter_router_gate_mean"] < 1
    for path in (("layer0", "mixer", "conv0_kernel"), ("layer0", "mixer", "conv1_kernel"),
                 ("layer2", "mixer", "temp"), ("layer2", "mixer", "v_prev", "kernel"),
                 ("layer1", "mixer", "router_down"), ("layer3", "mixer", "carry_scale"),
                 ("layer5", "mixer", "router_out"), ("layer1", "mixer", "w1")):
        assert moved(*path), path
    assert not moved("layer1", "mixer", "score_bias")
    layout, = [r["attrs"] for r in records if r["name"] == "model.layout"]
    trunk = sum(x.size for k, v in params.items() if k.startswith("layer")
                for x in jax.tree.leaves(v))
    assert layout["params_cca"] + layout["params_experts"] == trunk and layout["router"] == "mlp"
    assert layout["params_router"] == sum(
        x.size for k in ("layer1", "layer3", "layer5")
        for name, x in params[k]["mixer"].items() if name not in ("w1", "w2"))
    assert "shared_up" not in params["layer1"]["mixer"]
    paths = [r["attrs"] for r in records if r["name"] == "model.attention_path"]
    assert paths and all(p["path"] == "einsum" for p in paths)
    assert any("float32" in p["why"] for p in paths)
    assert module.program_scopes() == (CCA_SCOPE,)
    assert _module(pattern="M*E").program_scopes() == ()
    assert scoped_program_options("opt_update", CCA_SCOPE) != scoped_program_options("opt_update")


def test_what_the_net_refuses_it_refuses_by_name():
    obs = {"a": jnp.ones((2, 5))}
    for net, said in ((dict(pattern="C-", loops=2), "compressed convolutional attention"),
                      (dict(router="linear"), "'sigmoid', 'softmax' or 'mlp'")):
        module = _module(**net)
        with pytest.raises(ValueError, match=said):
            module.init(jax.random.PRNGKey(0), obs, module.initial_state((2,)))
    _, args, _, module = _geister({"batch_size": 2, "burn_in_steps": 0, "forward_steps": 8})
    with pytest.raises(ValueError, match="mesh"):
        TrainContext(module, args, make_mesh({"dp": 2}))


def test_the_published_layer_holds_what_the_issue_counted():
    """At the published widths, from shapes alone: a CCA sub-layer 5.58M, a
    router 0.66M, an expert 12.58M; five layers with 8 experts held 534.5M."""
    import json

    with open(os.path.join(REPO, "benchmark", "configs", "zaya1_8b.json")) as f:
        config = json.load(f)
    module = HybridNet(num_actions=214, with_return=True, **config["env_args"]["net_args"])
    layout = module.layout()
    d = 2048
    assert layout["params_cca"] // 5 - d == 5_242_880 + 3 * 1280 + 2 * 10 * 128 * 128 + 1280 + 2
    assert 655_000 < layout["params_router"] // 5 < 665_000
    assert layout["params_experts"] - layout["params_router"] - 5 * d == 5 * 8 * 3 * d * d
    assert 534.0e6 < layout["params_cca"] + layout["params_experts"] < 535.0e6
    state = jax.eval_shape(lambda: module.initial_state((1,)))["layers"][0]
    assert state["k"].shape == (1, 200, 2, 128) and state["tail"].shape == (1, 2, 1280)
    assert state["prev_v"].shape == (1, 128)


def test_a_weight_sum_wider_than_its_scope_is_taken_in_column_tiles(monkeypatch):
    """``ops/grouped_product.py`` ``_weight_sums`` at the cell's fused
    gate-and-up shape, (2048, 4096): one tile's float32 sum and output would
    be 64 MB (84 with a carried sum's tile), so the columns go in two tiles:
    the rule held to the published shapes by arithmetic (the described-v5e
    compile of them is tests/test_chip_compile.py's), and the kernel run (the
    interpreter) at a small shape under a scope cut down to it, where the same
    halving gives two column tiles: every group's sum is the plain one, a
    group without a block zeros."""
    assert 8 * 2048 * 4096 > grouped_product._SUMS_BYTES >= 8 * 4096 * 1536
    assert grouped_product._SUMS_BYTES >= 10 * 2688 * 1856      # with a carried sum's tile, whole too
    k, n = 64, 512
    monkeypatch.setattr(grouped_product, "_SUMS_BYTES", 8 * k * n // 2)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3 * 16, k), jnp.bfloat16)
    dy = jax.random.normal(jax.random.fold_in(key, 1), (3 * 16, n), jnp.bfloat16)
    owner = jnp.array([0, 2, 2], jnp.int32)
    got = grouped_product._weight_sums(x, dy, owner, 4, jnp.float32, True)
    blocks = lambda a: a.astype(jnp.float32).reshape(3, 16, -1)  # noqa: E731
    each = jnp.einsum("brk,brn->bkn", blocks(x), blocks(dy))
    want = jnp.stack([each[0], jnp.zeros_like(each[0]), each[1] + each[2], jnp.zeros_like(each[0])])
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
