"""``HybridNet`` with ``C`` layers (compressed convolutional attention) and the
``mlp`` router (top-1 gates that are not renormalised, a carry from one ``E``
layer's router to the next, no shared expert) against the plain reference of
``zaya1_8b`` (``benchmark/reference/zaya1_8b.py``), at a small size on the
CPU: both modes, the burn-in hand-off, the acting rows stepped in place, the
gradient a top-1 gate gives its router, the carry across ``remat: block``,
the two shares of the two-chip deployment, and the faults the comparison must
tell."""

import importlib.util
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import CCA_SCOPE, ExpertLayer, HybridNet
from handyrl_tpu.ops.routed_experts import choose
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import forward_prediction, pack_order
from handyrl_tpu.runtime import checkpoint
from handyrl_tpu.utils import trace
from handyrl_tpu.utils.compile_cache import scoped_program_options

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("zaya_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "zaya1_8b.py")

NET = dict(
    pattern="CECECE", d_model=32, norm_eps=1e-5,
    n_heads=4, n_kv_heads=2, head_dim=8, memory_len=6, rope_theta=1e4, rotary_factor=0.5,
    cca_time0=2, cca_time1=2,
    n_experts=8, top_k=1, expert_width=16, shared_width=0, routed_scale=1.0,
    experts_held=4, expert_offset=0, router="mlp", router_width=8, gated_experts=True,
)
HEADS = ("policy", "value", "return")
ROWS, STEPS = 3, 14
# float32 under "highest": the sound forward reads 4e-6 of a head's scale,
# the mildest fault below 2e-2
F32_TOLERANCE = 2e-4
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.06


def _config(**net):
    return {"name": "tiny_zaya", "env_args": {"env": "Geister", "net": "hybrid",
                                              "net_args": dict(NET, **net)}}


def _module(**net):
    return HybridNet(num_actions=7, with_return=True, **dict(NET, **net))


def _lively(params, seed=5):
    """Every vector leaf (biases, norm scales, ``carry_scale``, ``temp``,
    ``score_bias``) moved off its initial zeros or ones, so that leaving one
    out shows, and the routers' last maps scaled up, so that the scores, and
    not the choosing bias, spread the tokens over the experts."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(paths))

    def moved(path, leaf, key):
        name = path[-1].key
        if name == "router_out":
            return 8 * leaf
        noise = 0.03 if name == "score_bias" else 0.3
        return leaf + noise * jax.random.normal(key, leaf.shape) if leaf.ndim == 1 else leaf

    return jax.tree.unflatten(treedef, [moved(p, l, k) for (p, l), k in zip(paths, keys)])


@pytest.fixture(scope="module")
def toy():
    module = _module()
    obs = {"a": jax.random.normal(jax.random.PRNGKey(1), (ROWS, STEPS, 5))}
    params = _lively(module.init(jax.random.PRNGKey(0), {"a": jnp.ones((ROWS, 5))},
                                 module.initial_state((ROWS,)))["params"])
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (ROWS, STEPS)) < 0.6).astype(jnp.float32)
    assert 0.3 < float(mask.mean()) < 0.8
    return module, params, obs, mask, _reference(params, obs, mask, _config())


def _window(module, params, obs, mask, **how):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, o, m: module.apply(
            {"params": p}, o, None, seq=True, key_mask=m, **how))(params, obs, mask)


def _reference(params, obs, mask, config, **given):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, o, m, **kw: REFERENCE.forward(p, o, m, config, **kw))(
            params, obs, mask, **given)


def _apart(got, want, mask):
    """Largest difference over the observed steps, in units of a head's scale."""
    worst = 0.0
    for head in HEADS:
        a, b = np.asarray(got[head], np.float32), np.asarray(want[head], np.float32)
        diff = np.abs(a - b) * np.asarray(mask)[..., None]
        worst = max(worst, float(diff.max()) / max(1.0, float(np.abs(b).max())))
    return worst


# -- both modes against the plain reference ---------------------------------


@pytest.mark.parametrize("choices", ["free", "forced"])
def test_window_mode_is_the_reference_in_float32(toy, choices):
    """The whole window against the reference, its own choices and those
    the system made handed to it; the experts chosen are the same sets, and
    more than one of them is used."""
    module, params, obs, mask, want = toy
    got = _window(module, params, obs, mask)
    if choices == "forced":
        want = _reference(params, obs, mask, _config(), choices=got["choices"])
    assert _apart(got, want, mask) < 2e-5
    seen = np.asarray(mask) > 0
    for name, chosen in got["choices"].items():
        assert chosen.shape == (ROWS, STEPS, 1)
        np.testing.assert_array_equal(np.asarray(chosen)[seen], np.asarray(want["choices"][name])[seen])
    assert len({int(e) for c in got["choices"].values() for e in np.asarray(c)[seen].ravel()}) > 2
    gate = float(got["counters"]["router_gate_mean"])
    assert 1 / NET["n_experts"] < gate < 1


def _scan(module, params, obs, mask):
    """Step mode over the window by hand, as the train step's scan path does
    it: the hidden state is committed only where a step was observed."""
    rows, steps = mask.shape

    @jax.jit
    def step(hidden, obs_t, seen):
        out = module.apply({"params": params}, obs_t, hidden)
        new = out.pop("hidden")
        return jax.tree.map(lambda old, fresh: jnp.where(
            seen.reshape((rows,) + (1,) * (old.ndim - 1)) > 0, fresh, old), hidden, new), out

    hidden, outs = module.initial_state((rows,)), []
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            hidden, out = step(hidden, jax.tree.map(lambda x: x[:, t], obs), mask[:, t])
            outs.append(out)
    return {head: jnp.stack([o[head] for o in outs], axis=1) for head in HEADS}, hidden


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
    module, params, obs, mask, _ = toy
    assert all(jax.tree.leaves(module.rows_in_place(
        {"layers": module.initial_state((1,))["layers"]})))
    filled = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(x.size), x.shape),
        module.initial_state((ROWS, 2)))
    filled["pos"] = jnp.array([[3.0, 1.0], [7.0, 2.0], [0.0, 5.0]])
    player, begun = jnp.array([1, 0, 1], jnp.int32), jnp.array([False, False, True])
    step_obs = {"a": obs["a"][:, 0]}
    lanes = jnp.arange(ROWS)
    acting = jax.tree.map(lambda x: x[lanes, player] * ~begun.reshape(
        (-1,) + (1,) * (x.ndim - 2)), filled)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h: module.apply({"params": params}, step_obs, h))(acting)
        got = jax.jit(lambda h, r: module.apply({"params": params}, step_obs, h, rows=r))(
            dict(filled, pos=acting["pos"]), (player, begun))
    for head in HEADS:
        np.testing.assert_allclose(got[head], want[head], atol=1e-5)
    for new, old, stepped in zip(got["hidden"]["layers"], filled["layers"],
                                 want["hidden"]["layers"]):
        for name in new:
            np.testing.assert_allclose(new[name][lanes, player], stepped[name], atol=1e-5)
            rest = np.array(old[name][lanes, 1 - player])
            rest[np.asarray(begun)] = 0.0
            np.testing.assert_array_equal(new[name][lanes, 1 - player], rest)


# -- the router: a top-1 gate, the carry -------------------------------------


def test_the_router_gets_a_gradient_through_a_top1_gate(toy):
    """The gate is the chosen expert's own probability: every matrix of every
    router receives a gradient from the heads.  Renormalised over the chosen,
    as the other routers' gates are, a top-1 gate is 1.0 and gives none."""
    _, _, obs, mask, _ = toy
    module = _module(experts_held=8)    # uncut: whatever a token chooses is held
    params = _lively(module.init(jax.random.PRNGKey(0), {"a": jnp.ones((ROWS, 5))},
                                 module.initial_state((ROWS,)))["params"])

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


def _bf16_loss_and_grads(module, params, obs, mask, remat, burn_in):
    """The toy net's loss over the value and policy heads, its counters and
    every leaf's gradient, weights and stream in bfloat16 (the experts'
    products are then the grouped kernel's, in the interpreter)."""
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731

    def loss(p):
        out = module.apply({"params": to(p, jnp.bfloat16)}, to(obs, jnp.bfloat16), None, seq=True,
                           key_mask=mask, burn_in=burn_in, remat=remat)
        return (jnp.sum(jnp.square(out["value"].astype(jnp.float32) * mask[..., None]))
                + 0.1 * jnp.sum(out["policy"].astype(jnp.float32) * mask[..., None]),
                out["counters"])

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


@pytest.mark.parametrize("burn_in", [0, 4])
@pytest.mark.parametrize("remat", ["none", "block"])
def test_the_scan_reads_the_stacked_experts_in_place_and_is_the_unrolled_stack(
        toy, monkeypatch, remat, burn_in):
    """In bfloat16 the scan over periods closes over the ``E`` layers' stacked
    ``w1`` and ``w2``, the grouped kernel reads a period where it lies and
    the stacked gradient comes back through the sinks in the scan's carry
    (PERF.md, PR 51): loss and every leaf's gradient are the unrolled
    stack's (``passes``, which a pattern with no period takes) within the
    bfloat16 tolerance, with and without a checkpoint a layer, with and
    without a burn-in part.  ``counters["expert_stack_reads"]`` counts the
    routed layer applications that read in place: three periods a window
    part."""
    module, params, obs, mask, _ = toy
    (loss, counters), grads = _bf16_loss_and_grads(module, params, obs, mask, remat, burn_in)
    assert float(counters["expert_stack_reads"]) == (6 if burn_in else 3)
    assert float(counters["expert_passes"]) == 0
    monkeypatch.setattr(hybrid, "_period", lambda pattern: pattern)     # no period: unrolled
    (want, unrolled), want_grads = _bf16_loss_and_grads(module, params, obs, mask, remat, burn_in)
    assert "expert_stack_reads" not in unrolled
    for name in ("rows_held", "buffer_slots", "slots_run", "router_gate_mean"):
        assert float(counters[name]) == pytest.approx(float(unrolled[name]), rel=0.02), name
    # the toy's handful of rows lie in blocks of 16, all of which are run
    assert float(counters["rows_held"]) <= float(counters["slots_run"]) == float(
        counters["buffer_slots"])
    assert abs(float(loss) - float(want)) < BF16_TOLERANCE * max(1.0, abs(float(want)))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all()), path
        assert float(jnp.abs(a - b).max()) < BF16_TOLERANCE * max(1.0, float(jnp.abs(b).max())), path
    reached = [layer for layer in ("layer1", "layer3", "layer5")   # periods whose held experts got rows
               if float(jnp.abs(want_grads[layer]["mixer"]["w1"]).max()) > 0]
    assert len(reached) >= 2, reached
    for layer in reached:       # the sinks' cotangent came back for each of them
        for name in ("w1", "w2"):
            assert float(jnp.abs(grads[layer]["mixer"][name]).max()) > 0, (layer, name)


def test_a_float32_scan_and_a_stack_without_periods_read_no_stack_in_place(toy):
    """Float32 operands keep the plain block products and the scan's own
    slices of every leaf, and a ``MEME`` stack scans nothing: neither counts
    a read in place."""
    module, params, obs, mask, _ = toy
    out = jax.jit(lambda p: module.apply({"params": p}, obs, None, seq=True, key_mask=mask,
                                         burn_in=4))(params)
    assert "expert_stack_reads" not in out["counters"] and "rows_held" in out["counters"]
    plain = HybridNet(num_actions=7, pattern="MEME", d_model=32, n_experts=8, top_k=2,
                      expert_width=16, shared_width=16, experts_held=4)
    weights = plain.init(jax.random.PRNGKey(0), {"a": jnp.ones((ROWS, 5))},
                         plain.initial_state((ROWS,)))["params"]
    to = lambda tree: jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)  # noqa: E731
    out = jax.jit(lambda p: plain.apply({"params": p}, to(obs), None, seq=True, key_mask=mask,
                                        burn_in=4))(to(weights))
    assert "rows_held" in out["counters"]
    assert float(out["counters"].get("expert_stack_reads", 0.0)) == 0.0


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_reference(toy):
    """Offsets 0 and 4 of the two-chip deployment: each share scores and
    chooses over all eight experts with the whole router (the same choices,
    the same carry) and adds its own four experts' terms; the two terms add
    up to the layer whose eight experts are on one chip."""
    _, params, _, _, _ = toy
    whole = jax.tree.map(lambda x: x, params["layer3"]["mixer"])
    key = jax.random.PRNGKey(7)
    whole["w1"] = jax.random.normal(key, (8, 32, 32)) / 6
    whole["w2"] = jax.random.normal(jax.random.fold_in(key, 1), (8, 16, 32)) / 4
    whole["router_out"] = 3 * jax.random.normal(jax.random.fold_in(key, 4), (8, 8))
    h = jax.random.normal(jax.random.fold_in(key, 2), (2, 9, 32))
    carry = jax.random.normal(jax.random.fold_in(key, 3), (2, 9, 8))
    net = dict(NET, experts_held=8)
    with jax.default_matmul_precision("highest"):
        want, chosen, r = REFERENCE.experts(whole, h, carry, net)
        assert len(np.unique(chosen)) > 2 and (np.asarray(chosen) >= 4).any()
        total = 0.0
        for offset in (0, 4):
            share = dict(whole, w1=whole["w1"][offset:offset + 4], w2=whole["w2"][offset:offset + 4])
            layer = ExpertLayer(32, 8, 1, 16, 0, 1.0, 4, offset, "mlp", True, jnp.float32, 8, 1e-5)
            out, picked, counts, handed = jax.jit(
                lambda p: layer.apply({"params": p}, h, None, carry))(share)
            np.testing.assert_array_equal(picked, chosen)
            np.testing.assert_allclose(handed, r, atol=1e-5)
            assert int(counts["rows"].sum()) == int(((chosen >= offset) & (chosen < offset + 4)).sum())
            np.testing.assert_allclose(
                out, REFERENCE.experts(share, h, carry, dict(NET, expert_offset=offset))[0], atol=1e-5)
            total = total + out
    np.testing.assert_allclose(total, want, atol=1e-5)


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
    if fault == "unshifted_value":      # both value halves the current token's
        real = REFERENCE.back
        monkeypatch.setattr(REFERENCE, "back", lambda x, observed, pos, j: (
            x if x.shape[-1] == 8 else real(x, observed, pos, j)))
        faulty, theirs = module, params
    elif fault == "renormalised_gate":  # a top-1 gate of 1.0, as the other routers' ``choose`` makes it
        monkeypatch.setattr(hybrid, "choose", lambda *a, **how: choose(*a))
        faulty, theirs = module, params
    else:
        net, change = FAULTS[fault]
        faulty, theirs = _module(**net), change(params) if change else params
    got = _window(faulty, theirs, obs, mask)
    want = _reference(params, obs, mask, _config(), choices=got["choices"])
    assert _apart(got, want, mask) > 5 * F32_TOLERANCE


def test_the_eight_bit_control_fails_where_bfloat16_holds(toy):
    """bfloat16 weights and stream hold to the reference forced to their
    choices; weights rounded leaf by leaf to float8 e4m3 first do not."""
    module, params, obs, mask, _ = toy
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731
    sound, rough = [], []
    for seed in range(3):
        p = _lively(module.init(jax.random.PRNGKey(seed), {"a": jnp.ones((ROWS, 5))},
                                module.initial_state((ROWS,)))["params"], seed)
        for weights, readings in ((to(p, jnp.bfloat16), sound),
                                  (to(to(p, jnp.float8_e4m3fn), jnp.bfloat16), rough)):
            got = jax.jit(lambda w: module.apply(
                {"params": w}, to(obs, jnp.bfloat16), None, seq=True, key_mask=mask))(weights)
            want = _reference(p, obs, mask, _config(), choices=got["choices"])
            readings.append(_apart(got, want, mask))
    assert max(sound) < BF16_TOLERANCE < min(rough), (sound, rough)


# -- the system's entry points --------------------------------------------------


def _geister(train_args, seed=1, **net):
    config = _config(**dict({"memory_len": 200}, **net))
    cfg = normalize_args({"env_args": dict(config["env_args"]),
                          "train_args": dict(train_args, observation=True, seed=seed)})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(seed)
    np.random.seed(seed)
    env = make_env(args["env"])
    return config, args, env, env.net()


@pytest.fixture(scope="module")
def geister():
    from benchmark import traffic

    config, args, env, module = _geister(
        {"batch_size": 3, "burn_in_steps": 4, "forward_steps": 12})
    assert isinstance(module, HybridNet) and module.with_return and module.pattern == "CECECE"
    params = traffic.seeded_params(module, env, 1)
    batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
    assert 0.2 < float(np.mean(batch["observation_mask"])) < 0.8
    return config, args, module, params, batch


def test_the_scan_path_and_the_window_path_are_the_reference_on_geister(geister):
    """``forward_prediction`` through ``env.net()``: the whole-window call and
    the train step's scan over step mode, burn-in 4, against ``forward_rows``."""
    config, args, module, params, batch = geister
    with jax.default_matmul_precision("highest"):
        window = forward_prediction(module, params, batch, dict(args, seq_forward=True))
        scan = forward_prediction(module, params, batch, dict(args, seq_forward=False))
        want = REFERENCE.forward_rows(params, batch, config, 4, choices=window["choices"])
    observed = batch["observation_mask"][:, 4:]
    legal = (batch["action_mask"][:, 4:] == 0) & (batch["turn_mask"][:, 4:] > 0)
    for head in HEADS:
        keep = legal if head == "policy" else observed > 0
        for got in (window, scan):
            diff = np.where(keep, np.asarray(got[head]) - np.asarray(want[head]) * (
                1 if head == "policy" else observed), 0.0)
            assert float(np.abs(diff).max()) < 1e-4, head


def test_a_train_step_moves_every_new_part_and_a_checkpoint_brings_it_back(geister, tmp_path):
    """One ``TrainContext`` update under ``remat: block``: finite, the convolutions, the temperature, every router and the
    experts move, the step counts its gates; the state saved and loaded is
    the state; the layout says what the family added; and the step's cache
    key knows the new scope."""
    config, args, module, params, batch = geister
    args = dict(args, seq_forward=True, remat="block")
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        ctx = TrainContext(module, args, make_mesh({"dp": 1}))
        device_batch = ctx.put_batch(batch)
        before = jax.device_get(params)
        state, metrics = ctx.train_step(ctx.init_state(params), device_batch, 1e-3)
        metrics, after = jax.device_get(metrics), jax.device_get(state["params"])
    finally:
        trace.shutdown()
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    assert metrics["counter_rows_held"] > 0 and metrics["counter_expert_passes"] == 0
    assert 1 / 8 < metrics["counter_router_gate_mean"] < 1
    moved = lambda *path: not np.allclose(  # noqa: E731
        np.asarray(_at(after, path)), np.asarray(_at(before, path)))
    for path in (("layer0", "mixer", "conv0_kernel"), ("layer0", "mixer", "conv1_kernel"),
                 ("layer2", "mixer", "temp"), ("layer2", "mixer", "v_prev", "kernel"),
                 ("layer1", "mixer", "router_down"), ("layer3", "mixer", "carry_scale"),
                 ("layer5", "mixer", "router_out"), ("layer1", "mixer", "w1")):
        assert moved(*path), path
    assert not moved("layer1", "mixer", "score_bias")

    checkpoint.save_train_state(str(tmp_path / "state.ckpt"), state)
    loaded = checkpoint.load_train_state(str(tmp_path / "state.ckpt"), jax.device_get(state))
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(jax.device_get(state))):
        np.testing.assert_array_equal(a, b)

    records = trace.read_trace(str(tmp_path / "trace.jsonl"))
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


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


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


def test_a_weight_sum_wider_than_its_scope_is_taken_in_column_tiles():
    """``ops/grouped_product.py`` ``_weight_sums`` at the cell's fused
    gate-and-up shape, (2048, 4096): one tile's float32 sum and output would
    be 64 MB (84 with a carried sum's tile), so the columns go in two tiles (the interpreter here; the
    described-v5e compile is tests/test_chip_compile.py's): every group's sum
    is the plain one, a group without a block zeros."""
    from handyrl_tpu.ops import grouped_product

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3 * 16, 2048), jnp.bfloat16)
    dy = jax.random.normal(jax.random.fold_in(key, 1), (3 * 16, 4096), jnp.bfloat16)
    owner = jnp.array([0, 2, 2], jnp.int32)
    got = grouped_product._weight_sums(x, dy, owner, 4, jnp.float32, True)
    assert 8 * 2048 * 4096 > grouped_product._SUMS_BYTES >= 8 * 4096 * 1536
    assert grouped_product._SUMS_BYTES >= 10 * 2688 * 1856      # with a carried sum's tile, whole too
    blocks = lambda a: a.astype(jnp.float32).reshape(3, 16, -1)  # noqa: E731
    each = jnp.einsum("brk,brn->bkn", blocks(x), blocks(dy))
    want = jnp.stack([each[0], jnp.zeros_like(each[0]), each[1] + each[2], jnp.zeros_like(each[0])])
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
