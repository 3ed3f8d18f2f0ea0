"""``HybridNet`` with local ``W`` and global ``*`` attention layers in one
pattern (per-head q/k norms, an output gate, the local ones rotated and
windowed, the global ones neither), a dense ``-`` layer and gated
``sigmoid``-routed ``E`` layers with a shared expert, between sandwich norms
behind a scaled encoder, against the plain reference of ``trinity_mini``
(``benchmark/reference/trinity_mini.py``), at a small size on the CPU: a
window, a step and a step with ``rows``, a window of 4 that binds where a
row observes 16 steps, the two ring lengths of the hidden pytree, what the
step counts of the window, and the faults the comparison must tell."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import (ATTN_GATE_SCOPE, ATTN_PROJ_SCOPE, QK_NORM_SCOPE,
                                       HybridNet)
from handyrl_tpu.ops import attention_core
from handyrl_tpu.ops.routed_experts import choose
from handyrl_tpu.parallel.train_step import pack_order
from handyrl_tpu.utils.compile_cache import scoped_program_options
from nets import REPO, _apart, _bf16_loss_and_grads, _load, _scan, _window

# heads x head_dim as wide as the stream, so that a gate applied after ``o``
# can be written at all; a window a fifth of the steps, a ring that holds them
NET = dict(
    pattern="W-*EWE", d_model=32, norm_eps=1e-5, n_heads=4, n_kv_heads=2, head_dim=8,
    window=4, memory_len=24, rope_theta=1e4, rope_local_only=True, qk_norm=True, attn_gate=True,
    sandwich=True, embed_scale=32 ** 0.5, mlp_width=48,
    n_experts=16, top_k=4, expert_width=16, shared_width=16, routed_scale=2.826,
    experts_held=8, expert_offset=4, router="sigmoid", gated_experts=True,
)
# ``lively``: the routers scaled up, ``q_norm`` and ``k_norm`` moved with every
# vector (moved past a rotation, one shows); a row observes four windows of steps
TRINITY = nets.Family(
    "tiny_trinity", NET, "trinity_mini.py", on_geister={"memory_len": 200, "window": 6},
    lively=functools.partial(nets._lively, routers={"router": 4}, bias_noise=0.1),
    steps=20, observed=0.8)
REFERENCE = TRINITY.REFERENCE
_module, _init, _reference = (functools.partial(f, TRINITY) for f in (
    nets._module, nets._init, nets._reference))
_inputs = functools.partial(nets._inputs, TRINITY)
ROWS, STEPS = TRINITY.rows, TRINITY.steps
# float32 under "highest": the sound forward reads 1e-6 of a head's scale
F32_TOLERANCE = 2e-4
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.05


@pytest.fixture(scope="module")
def toy():
    made = nets._toy(TRINITY)
    mask = made[3]
    # a row observes sixteen steps or more: four times the window, under the ring
    assert 16 <= int(mask.sum(axis=1).max()) <= NET["memory_len"] and float(mask.mean()) < 0.9
    return made


# -- the three modes against the plain reference --------------------------------


@pytest.mark.parametrize("choices", ["free", "forced"])
def test_window_mode_is_the_reference_in_float32(toy, choices):
    """The whole window against the reference (a loop over query steps, every
    query head its own copy of its key head), its own choices and those the
    system made handed to it; the experts chosen are the same sets, and the
    gate, the two per-head norms and the four norms a layer are parameters."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask)
    if choices == "forced":
        want = _reference(params, obs, mask, choices=got["choices"])
    assert _apart(got, want, mask) < 2e-5
    seen = np.asarray(mask) > 0
    assert sorted(got["choices"]) == ["layer3", "layer5"]
    for name, chosen in got["choices"].items():
        assert chosen.shape == (ROWS, STEPS, 4)
        np.testing.assert_array_equal(np.sort(np.asarray(chosen)[seen], axis=-1),
                                      np.sort(np.asarray(want["choices"][name])[seen], axis=-1))
    assert len({int(e) for c in got["choices"].values() for e in np.asarray(c)[seen].ravel()}) > 6
    for layer in ("layer0", "layer2", "layer4"):
        mixer = params[layer]["mixer"]
        assert sorted(mixer) == ["gate", "k", "k_norm", "o", "q", "q_norm", "v"]
        assert mixer["gate"]["kernel"].shape == (32, 32) and mixer["k"]["kernel"].shape == (32, 16)
        assert mixer["q_norm"].shape == mixer["k_norm"].shape == (8,)
        assert sorted(params[layer]) == ["mixer", "norm", "norm_out"]


def test_the_steps_are_the_window_and_the_hidden_holds_two_ring_lengths(toy):
    """Twenty steps of step mode, the local layers' rings of four evicting
    and the global layer's of 24 not, equal the window and the reference; the
    hidden pytree holds a ring as long as each layer sees back, and the layout
    says so."""
    module, params, obs, mask, want = toy
    got, hidden = _scan(module, params, obs, mask)
    assert _apart(got, want, mask) < 2e-5
    assert _apart(got, _window(module, params, obs, mask), mask) < 2e-5
    rings = [state["k"].shape[1] for state in hidden["layers"] if state]
    assert rings == [4, 24, 4] == module.layout()["rings"]
    for kind, state in zip(NET["pattern"], hidden["layers"]):
        if kind in "W*":
            assert sorted(state) == ["k", "v"] and state["v"].shape == (
                ROWS, module.ring(kind), 2, 8)
        else:
            assert not state
    # a looped stack holds one ring an application, by kind
    assert _module(pattern="W*", loops=2).layout()["rings"] == [4, 24, 4, 24]


@pytest.mark.parametrize("burn_in", [1, 7])
def test_a_window_split_at_burn_in_is_the_unsplit_window(toy, burn_in):
    """The burn-in steps as a window of their own hand on their keys (the
    local layers' rotated) and values: the forward steps read what the unsplit
    window reads, a window reaches back across the hand-off, and the step
    counts the same pairs either way; packed by the host likewise."""
    module, params, obs, mask, want = toy
    whole = _window(module, params, obs, mask)
    got = _window(module, params, obs, mask, burn_in=burn_in, remat="block")
    assert _apart(got, want, mask) < 2e-5
    for name in ("causal_pairs", "window_pairs_cut", "attn_gate_mean"):
        assert float(got["counters"][name]) == pytest.approx(float(whole["counters"][name]))
    seen = np.asarray(mask) > 0
    order = {"burn_in": pack_order(seen[:, :burn_in], int(seen[:, :burn_in].sum(axis=1).max())),
             "forward": pack_order(seen[:, burn_in:], int(seen[:, burn_in:].sum(axis=1).max()))}
    packed = _window(module, params, obs, mask, burn_in=burn_in, packed_order=order)
    assert _apart(packed, want, mask) < 2e-5 and float(packed["counters"]["packed_dropped"]) == 0
    assert float(packed["counters"]["packed_slots"]) < float(got["counters"]["packed_slots"])
    assert float(packed["counters"]["window_pairs_cut"]) == float(
        whole["counters"]["window_pairs_cut"])


def test_rows_steps_the_acting_players_rings_in_place(toy):
    """Step mode with ``rows``: the hidden tree per (row, player), the acting
    player's rings of either length read and written where they lie (as zeros
    where the row's game has just begun), the other player's left as they
    were, or zeroed where it begins."""
    module, params, obs, _, _ = toy
    nets._rows_stepped_in_place(module, params, obs)


# -- the window where it binds ----------------------------------------------------


def test_a_window_of_four_binds_in_the_local_layers_and_not_in_the_global(toy):
    """Sixteen observed steps and more in a row: a wider window moves the
    outputs (the local layers' mask binds, in the window and in the steps
    alike: both are the reference above), a longer ring for the global layer
    moves nothing, and ``window_pairs_cut`` is what the masks cut: per local
    layer, the pairs ``_seen_from`` lets through without a window less those
    it lets through with it."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask)
    assert _apart(_window(_module(window=5), params, obs, mask), got, mask) > 5 * F32_TOLERANCE
    assert _apart(_window(_module(window=24), params, obs, mask), got, mask) > 5 * F32_TOLERANCE
    assert _apart(_window(_module(memory_len=40), params, obs, mask), got, mask) < 2e-6
    count = jnp.asarray(mask.sum(axis=1), jnp.int32)
    valid = jnp.arange(STEPS)[None, :] < count[:, None]      # the packed rows' prefix
    none_before = jnp.zeros((ROWS,), jnp.int32)
    through = lambda reach: int(hybrid._seen_from(     # noqa: E731  query i of a row sees key j
        none_before, 0, valid, reach)[np.asarray(valid)].sum())
    causal, windowed = through(STEPS), through(NET["window"])
    assert windowed < causal == int((count * (count + 1) // 2).sum())
    local = NET["pattern"].count("W")
    assert float(got["counters"]["causal_pairs"]) == local * causal
    assert float(got["counters"]["window_pairs_cut"]) == local * (causal - windowed) > 0
    # a window that holds every step cuts nothing, as the published one in the cell
    slack = _window(_module(window=2048), params, obs, mask)["counters"]
    assert float(slack["window_pairs_cut"]) == 0 and float(slack["causal_pairs"]) == local * causal
    assert 0.3 < float(got["counters"]["attn_gate_mean"]) < 0.7
    assert "window_pairs_cut" not in _window(_module(pattern="*-"), _init(_module(pattern="*-")),
                                             obs, mask)["counters"]


@pytest.mark.parametrize("rotated", [True, False], ids=["local", "global"])
def test_the_kernel_and_the_einsum_lines_mask_the_same_window(rotated):
    """A window part of 64 queries behind 8 handed keys at the kernel's
    widths (bfloat16, heads of 128), the window 4 against a window that holds
    the row: ``_whole_rows`` (the kernel, here in the Pallas interpreter) and
    ``_grouped_rows`` with ``_rope`` agree under either, and the two windows
    differ under both, rotated as a local layer is or not; and a layer *told*
    its reach and whether it turns (a scanned period's: one program for a
    local and a global layer) computes, forward and backward, what the layer
    whose window and rotation are static computes."""
    n, length, past, hq, hk, d = 2, 64, 8, 2, 1, 128
    theta = 1e4 if rotated else 0.0
    key = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (n, length, heads * d), jnp.bfloat16)
               for i, heads in enumerate((hq, hk, hk)))
    state = {"k": jax.random.normal(jax.random.fold_in(key, 5), (n, past, hk, d), jnp.bfloat16),
             "v": jax.random.normal(jax.random.fold_in(key, 6), (n, past, hk, d), jnp.bfloat16),
             "n": jnp.array([8, 3], jnp.int32)}
    valid = jnp.arange(length)[None, :] < jnp.array([[64], [41]])
    assert attention_core.fits(q.dtype, length, past, hq, hk, d)

    def lines(window):
        at = state["n"][:, None] + jnp.arange(length)[None, :]
        q5, k4 = q.reshape(n, length, hk, hq // hk, d), k.reshape(n, length, hk, d)
        if rotated:
            q5, k4 = hybrid._rope(q5, at, theta), hybrid._rope(k4, at, theta)
        out, new = hybrid._grouped_rows(q5, k4, v.reshape(n, length, hk, d), state, valid, False,
                                        window)
        return out.reshape(n, length, hq * d), new

    seen = np.asarray(valid)
    outs = {}
    for window in (4, 128):
        (kernel, kept), (einsum, want) = hybrid._whole_rows(
            q, k, v, state, valid, hq, window, theta), lines(window)
        a, b = np.asarray(kernel, np.float32)[seen], np.asarray(einsum, np.float32)[seen]
        assert np.abs(a - b).max() < 0.05 * max(1.0, np.abs(b).max())
        np.testing.assert_allclose(np.asarray(kept["k"], np.float32),
                                   np.asarray(want["k"], np.float32), atol=0.05)
        outs[window] = a, b
    for line in (0, 1):
        assert np.abs(outs[4][line] - outs[128][line]).max() > 0.5

    def through(told):
        """(out, new keys, gradients) of the kernel, window 4: static, or told."""
        def loss(q, k, v, past_k, past_v):
            how = dict(k=past_k, v=past_v, n=state["n"])
            if told:
                how.update(reach=jnp.int32(4), turn=jnp.int32(rotated))
            out, new = hybrid._whole_rows(q, k, v, how, valid, hq, 128 if told else 4,
                                          1e4 if told else theta)
            return (out.astype(jnp.float32) * seen[..., None]).sum() + new["k"].astype(
                jnp.float32).sum(), (out, new["k"])
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
            q, k, v, state["k"], state["v"])

    for a, b in zip(jax.tree.leaves(through(True)), jax.tree.leaves(through(False))):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# -- three periods behind four leading layers: a scan ------------------------------


def test_the_periods_behind_the_leading_layers_scan_and_are_the_unrolled_stack(monkeypatch):
    """``W-*EWEWEWE``, the published cell's pattern: the two leading layers
    run unrolled and the four attention-and-experts periods behind them as a
    ``lax.scan`` whose one attention layer is told, period by period, that it
    is global and unrotated (the first) or local and rotated (the others),
    rings stacked by period across the burn-in hand-off: in float32 the window
    is the reference; in bfloat16, where the grouped kernel reads the stacked
    experts a period where it lies, loss and every leaf's gradient are the
    unrolled stack's within the bfloat16 tolerance, the gates and norms too."""
    module = _module(pattern="W-*EWEWEWE")
    params = _init(module)
    obs, mask = _inputs()
    assert hybrid._periods("W-*EWEWEWE") == (4, "WE") and module.scanned_periods() == (2, "WE")
    assert _module().scanned_periods() == (6, "") and _module(
        pattern="M*EM*EM*E").scanned_periods() == (0, "M*E")
    got = _window(module, params, obs, mask, burn_in=5, remat="block")
    want = _reference(params, obs, mask, choices=got["choices"], pattern="W-*EWEWEWE")
    assert _apart(got, want, mask) < 2e-5
    assert sorted(got["choices"]) == ["layer3", "layer5", "layer7", "layer9"]
    assert float(got["counters"]["causal_pairs"]) == 4 * int(
        (mask.sum(axis=1) * (mask.sum(axis=1) + 1) // 2).sum())
    (value, counters), grads = _bf16_loss_and_grads(module, params, obs, mask, "block", 5)
    assert float(counters["expert_stack_reads"]) == 8       # four periods, two window parts
    monkeypatch.setattr(hybrid, "_periods", lambda pattern: (len(pattern), ""))     # unrolled
    (want, unrolled), want_grads = _bf16_loss_and_grads(module, params, obs, mask, "block", 5)
    assert "expert_stack_reads" not in unrolled
    for name in ("causal_pairs", "window_pairs_cut"):
        assert float(counters[name]) == float(unrolled[name])
    assert float(counters["attn_gate_mean"]) == pytest.approx(
        float(unrolled["attn_gate_mean"]), rel=0.02)
    assert abs(float(value) - float(want)) < BF16_TOLERANCE * max(1.0, abs(float(want)))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all()), path
        # a gradient sums bfloat16 terms over rows and steps in another order: twice the
        # forward's room; and where that rounding turns one token's fourth and fifth
        # expert (one of some 200 choice sets does, in most seeds) a whole row of the
        # turned experts' leaves moves with it: twice that again for those
        room = 4 if path[-1].key in ("w1", "w2", "router") else 2
        assert float(jnp.abs(a - b).max()) < room * BF16_TOLERANCE * max(
            1.0, float(jnp.abs(b).max())), path
    for name in ("gate", "q", "o"):
        assert float(jnp.abs(grads["layer8"]["mixer"][name]["kernel"]).max()) > 0
    assert float(jnp.abs(grads["layer6"]["mixer"]["k_norm"]).max()) > 0


# -- the faults the comparison must tell ---------------------------------------


def _gate_after_o(mix, g, p):
    return (mix @ p["o"]["kernel"]) * jax.nn.sigmoid(g)


def _norm_after_the_rotation(x, scale, pos, local, net):
    x = REFERENCE.turned(x, pos, float(net["rope_theta"])) if local else x
    return REFERENCE.rms_norm(x, scale, float(net["norm_eps"]))


def _bias_in_the_weights(scores, bias, k, scale):
    return choose(scores + bias, jnp.zeros_like(bias), k, scale)


# the system's options, the system's lines, or the reference's equations
FAULTS = {
    "no_gate": {"net": dict(attn_gate=False)},
    "gate_after_o": {"reference": ("closed", _gate_after_o)},
    "no_qk_norm": {"net": dict(qk_norm=False)},
    "norm_after_the_rotation": {"reference": ("prepared", _norm_after_the_rotation)},
    "a_rotated_global_layer": {"net": dict(rope_local_only=False)},
    "an_unrotated_local_layer": {"net": dict(rope_theta=0.0)},
    "a_window_one_longer": {"net": dict(window=5)},
    "a_window_one_shorter": {"net": dict(window=3)},
    "no_renormalisation": {"system": ("choose", lambda *a: choose(*a, renormalise=False))},
    "top_k_6_of_8": {"net": dict(top_k=3)},       # three of the toy's four: as six of eight
    "the_bias_added_to_the_weights": {"system": ("choose", _bias_in_the_weights)},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_layer_with_one_thing_wrong_fails_the_comparison(toy, fault, monkeypatch):
    """Each fault, as an option of the system, in the system's lines or in
    the reference's equations, reads over the float32 limit that the sound
    pair is a hundred times under."""
    _, params, obs, mask, want = toy
    how = FAULTS[fault]
    if "system" in how:
        monkeypatch.setattr(hybrid, *how["system"])
    if "reference" in how:
        monkeypatch.setattr(REFERENCE, *how["reference"])
        want = _reference(params, obs, mask, fresh=True)
    # a patched side is traced anew, under the patch
    got = _window(_module(**how.get("net", {})), params, obs, mask, fresh="system" in how)
    assert _apart(got, want, mask) > 5 * F32_TOLERANCE


def test_the_eight_bit_control_fails_where_bfloat16_holds(toy):
    """bfloat16 weights and stream hold to the reference forced to their
    choices; weights rounded leaf by leaf to float8 e4m3 first do not."""
    module, _, obs, mask, _ = toy
    sound, rough = nets._eight_bit_readings(TRINITY, module, obs, mask)
    assert max(sound) < BF16_TOLERANCE < min(rough), (sound, rough)


# -- the system's entry points --------------------------------------------------


@pytest.fixture(scope="module")
def geister():
    return nets._geister_windows(TRINITY, batch_size=3, burn_in_steps=4, forward_steps=20)


def test_the_scan_path_and_the_window_path_are_the_reference_on_geister(geister):
    """``forward_prediction`` through ``env.net()``: the whole-window call and
    the train step's scan over step mode, burn-in 4, a window of 6 that binds,
    against ``forward_rows``."""
    window = nets._both_paths_on_geister(TRINITY, geister)
    assert float(window["counters"]["window_pairs_cut"]) > 0


def test_a_train_step_moves_every_new_part_and_a_checkpoint_brings_it_back(geister, tmp_path):
    """One ``TrainContext`` update under ``remat: block``: finite, the five
    projections, both per-head norms, all four norms of a layer, the dense
    layer, every router, the shared expert and the experts move, the choosing
    bias does not; the step counts its pairs and its gates; the state saved
    and loaded is the state; the layout says each ring's length; and the
    step's cache key knows the new scopes."""
    _, _, module, params, _ = geister
    metrics, moved, records = nets._update_and_checkpoint(geister, tmp_path)
    assert metrics["counter_rows_held"] > 0
    assert 0 < metrics["counter_window_pairs_cut"] < metrics["counter_causal_pairs"]
    assert 0.3 < metrics["counter_attn_gate_mean"] < 0.7
    for path in (("layer0", "mixer", "q", "kernel"), ("layer0", "mixer", "gate", "kernel"),
                 ("layer2", "mixer", "k", "kernel"), ("layer2", "mixer", "v", "kernel"),
                 ("layer4", "mixer", "o", "kernel"), ("layer0", "mixer", "q_norm"),
                 ("layer2", "mixer", "k_norm"), ("layer4", "norm"), ("layer4", "norm_out"),
                 ("layer5", "norm"), ("layer5", "norm_out"), ("layer1", "mixer", "up", "kernel"),
                 ("layer3", "mixer", "router"), ("layer5", "mixer", "shared_up", "kernel"),
                 ("layer3", "mixer", "w1"), ("layer5", "mixer", "w2")):
        assert moved(*path), path
    assert not moved("layer3", "mixer", "score_bias")
    layout, = [r["attrs"] for r in records if r["name"] == "model.layout"]
    size = lambda *names: sum(  # noqa: E731
        x.size for name in names for x in jax.tree.leaves(params[name]))
    assert layout["rings"] == [6, 200, 6]
    assert layout["params_attention"] == size("layer0", "layer2", "layer4")
    assert layout["params_mlp"] == size("layer1") and layout["params_experts"] == size(
        "layer3", "layer5")
    assert module.program_scopes() == (ATTN_PROJ_SCOPE, QK_NORM_SCOPE, ATTN_GATE_SCOPE)
    # the older nets' programs keep the cache keys they had
    assert _module(pattern="M*E", qk_norm=False, attn_gate=False).program_scopes() == ()
    assert scoped_program_options("opt_update", *module.program_scopes()) != (
        scoped_program_options("opt_update"))


def test_the_counter_counts_the_windows_cut_and_not_the_rings(toy):
    """``window_pairs_cut`` is held against ``window`` alone: a ``memory_len``
    shorter than the window masks pairs in every attention layer (a departure of
    the configuration's, both kinds'), and the counter leaves those out."""
    module, params, obs, mask, _ = toy
    got = _window(module, params, obs, mask)
    short = _window(_module(window=2048, memory_len=NET["window"]), params, obs, mask)
    assert _apart(short, _window(_module(window=2048), params, obs, mask), mask) > 5 * F32_TOLERANCE
    assert float(short["counters"]["window_pairs_cut"]) == 0
    assert float(short["counters"]["causal_pairs"]) == float(got["counters"]["causal_pairs"])
    both = _window(_module(memory_len=NET["window"] - 1), params, obs, mask)["counters"]
    assert float(both["window_pairs_cut"]) == float(got["counters"]["window_pairs_cut"]) > 0


def test_what_the_net_refuses_it_refuses_by_name():
    module = _module(window=0)
    with pytest.raises(ValueError, match="a local attention layer needs a window"):
        module.init(jax.random.PRNGKey(0), {"a": jnp.ones((2, 5))}, module.initial_state((2,)))


def test_the_published_layer_holds_what_the_issue_counted():
    """At the published widths, from shapes alone: an attention sub-layer
    27.26M (q, the gate and o 8.39M each, k and v 1.05M each) beside its two
    norms of 128, the dense MLP 37.75M, an expert layer's router 0.26M, shared
    expert 6.29M and 16 held experts 100.66M; the trunk 602.9M, and
    ``flops/afmoe.py`` counts the same net; every ring holds a Geister game's
    200 plies, the local layers' too, since the window of 2,048 is the longer."""
    with open(os.path.join(REPO, "benchmark", "configs", "trinity_mini.json")) as f:
        config = json.load(f)
    net = config["env_args"]["net_args"]
    assert net["embed_scale"] == pytest.approx(2048 ** 0.5) and net["window"] == 2048
    module = HybridNet(num_actions=214, with_return=True, **net)
    layout = module.layout()
    d = 2048
    assert layout["params_attention"] // 5 - 2 * d - 256 == 3 * 8_388_608 + 2 * 1_048_576
    assert layout["params_mlp"] - 2 * d == 37_748_736
    assert layout["params_router"] // 4 == 262_144 + 128
    assert layout["params_experts"] // 4 - 2 * d - 262_272 == 6_291_456 + 16 * 6_291_456
    trunk = layout["params_attention"] + layout["params_mlp"] + layout["params_experts"]
    assert 602.8e6 < trunk < 603.1e6 and layout["rings"] == [200] * 5
    flops = _load("flops", "afmoe.py")
    shapes = jax.eval_shape(lambda key: module.init(
        key, {"a": jnp.ones((1, 270))}, module.initial_state((1,)))["params"], jax.random.PRNGKey(0))
    assert flops.parameters(net, 270, 214, 2) == sum(x.size for x in jax.tree.leaves(shapes))
    state = jax.eval_shape(lambda: module.initial_state((1,)))["layers"]
    assert state[0] == {name: jax.ShapeDtypeStruct((1, 200, 4, 128), jnp.float32)
                        for name in ("k", "v")}
    assert not state[1] and not state[3]
