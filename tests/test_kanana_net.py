"""``HybridNet`` with ``L`` layers (multi-head latent attention: a ring of
latents with one rotated key part, keys wider than values), a dense ``-``
layer and gated ``sigmoid``-routed ``E`` layers with a shared expert in one
pattern, against the plain reference of ``kanana_2_30b_a3b``
(``benchmark/reference/kanana_2_30b_a3b.py``), at a small size on the CPU:
the expanded form of a window against the absorbed form of a step, the
burn-in hand-off of latents, the acting rows' rings stepped in place, the
eight shares of the eight-chip deployment, and the faults the comparison
must tell."""

import functools
import importlib.util
import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import (MLA_CORE_SCOPE, MLA_PROJ_SCOPE, ExpertLayer, HybridNet,
                                       LatentAttention, Layer)
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import forward_prediction, pack_order
from handyrl_tpu.runtime import checkpoint
from handyrl_tpu.utils import trace
from handyrl_tpu.utils.compile_cache import scoped_program_options

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("kanana_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "kanana_2_30b_a3b.py")

# a value narrower than the unrotated key part, as the published 128 is
# narrower than 192: a head split at the wrong place shows
NET = dict(
    pattern="L-LELE", d_model=32, norm_eps=1e-6,
    n_heads=4, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=6, kv_latent=12, memory_len=6,
    rope_theta=1e4, mlp_width=48,
    n_experts=8, top_k=3, expert_width=16, shared_width=24, routed_scale=2.448,
    experts_held=4, expert_offset=0, router="sigmoid", gated_experts=True,
)
SLOT = NET["kv_latent"] + NET["qk_rope_dim"]        # what a ring keeps of a step
HEADS = ("policy", "value", "return")
ROWS, STEPS = 3, 14
# float32 under "highest": the sound forward reads 2e-6 of a head's scale,
# the mildest fault below 3e-3
F32_TOLERANCE = 2e-4
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.05


def _config(**net):
    return {"name": "tiny_kanana", "env_args": {"env": "Geister", "net": "hybrid",
                                                "net_args": dict(NET, **net)}}


def _module(**net):
    return HybridNet(num_actions=7, with_return=True, **dict(NET, **net))


def _lively(params, seed=5):
    """Every vector leaf (biases, norm scales, ``kv_norm``, ``score_bias``)
    moved off its initial zeros or ones, so that leaving one out shows, and the
    routers scaled up, so that the scores spread the tokens over the experts."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(paths))

    def moved(path, leaf, key):
        name = path[-1].key
        if name == "router":
            return 4 * leaf
        noise = 0.03 if name == "score_bias" else 0.3
        return leaf + noise * jax.random.normal(key, leaf.shape) if leaf.ndim == 1 else leaf

    return jax.tree.unflatten(treedef, [moved(p, l, k) for (p, l), k in zip(paths, keys)])


@functools.lru_cache(maxsize=None)
def _seeded(module):
    """``module``'s lively parameters from a seed, traced and compiled once a
    net: every case that wants weights of a net shares the one built here."""
    return jax.jit(lambda seed: _lively(
        module.init(jax.random.PRNGKey(seed), {"a": jnp.ones((ROWS, 5))},
                    module.initial_state((ROWS,)))["params"], seed + 5))


def _init(module, seed=0):
    return _seeded(module)(seed)


@pytest.fixture(scope="module")
def toy():
    module = _module()
    obs = {"a": jax.random.normal(jax.random.PRNGKey(1), (ROWS, STEPS, 5))}
    params = _init(module)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (ROWS, STEPS)) < 0.6).astype(jnp.float32)
    # more tokens in a row than the ring has slots: it wraps
    assert 0.3 < float(mask.mean()) < 0.8 and int(mask.sum(axis=1).max()) > NET["memory_len"]
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
        want = _reference(params, obs, mask, _config(), choices=got["choices"])
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


# -- three or more periods behind the leading layers: a scan -------------------


@pytest.mark.parametrize("remat", ["none", "block"])
def test_the_periods_behind_the_leading_layers_scan_and_are_the_unrolled_stack(monkeypatch, remat):
    """``L-LELELE``: the two leading layers run unrolled and the three ``LE``
    periods behind them as a ``lax.scan`` (the published cell's four), the
    latents stacked by period across the burn-in hand-off: in float32 the
    window is the reference; in bfloat16, where the grouped kernel reads the
    stacked experts a period where it lies, loss and every leaf's gradient are
    the unrolled stack's within the bfloat16 tolerance."""
    module = _module(pattern="L-LELELE")
    params = _init(module)
    obs = {"a": jax.random.normal(jax.random.PRNGKey(1), (ROWS, STEPS, 5))}
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (ROWS, STEPS)) < 0.6).astype(jnp.float32)
    assert hybrid._periods("L-LELELE") == (2, "LE") and hybrid._periods("L-LELE") == (6, "")
    assert hybrid._periods("CECECE") == (0, "CE") and hybrid._periods("MEMEM*EME") == (9, "")
    got = _window(module, params, obs, mask, burn_in=4, remat=remat)
    want = _reference(params, obs, mask, _config(pattern="L-LELELE"), choices=got["choices"])
    assert _apart(got, want, mask) < 2e-5 and sorted(got["choices"]) == ["layer3", "layer5", "layer7"]
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731

    def loss(p):
        out = module.apply({"params": to(p, jnp.bfloat16)}, to(obs, jnp.bfloat16), None, seq=True,
                           key_mask=mask, burn_in=4, remat=remat)
        return (jnp.sum(jnp.square(out["value"].astype(jnp.float32) * mask[..., None]))
                + 0.1 * jnp.sum(out["policy"].astype(jnp.float32) * mask[..., None]),
                out["counters"])

    (value, counters), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    assert float(counters["expert_stack_reads"]) == 6       # three periods, two window parts
    monkeypatch.setattr(hybrid, "_periods", lambda pattern: (len(pattern), ""))     # unrolled
    (want, unrolled), want_grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    assert "expert_stack_reads" not in unrolled
    assert float(counters["rows_held"]) == pytest.approx(float(unrolled["rows_held"]), rel=0.02)
    assert abs(float(value) - float(want)) < BF16_TOLERANCE * max(1.0, abs(float(want)))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all()), path
        # a gradient sums bfloat16 terms over rows and steps in another order: twice the forward's room
        assert float(jnp.abs(a - b).max()) < 2 * BF16_TOLERANCE * max(
            1.0, float(jnp.abs(b).max())), path
    assert float(jnp.abs(grads["layer6"]["mixer"]["kv_b"]).max()) > 0


# -- the deployment: eight chips share each layer ------------------------------


def _kanana_share():
    """kanana_2_30b_a3b's: top-6, scale 2.448, behind its ``L`` mixer."""
    mixer = LatentAttention(32, 4, 8, 4, 6, 12, 6, 1e4, 1e-6)
    empty = {"latent": jnp.zeros((2, 0, SLOT)), "n": jnp.zeros((2,), jnp.int32)}
    return REFERENCE, NET, 6, 2.448, mixer, empty, REFERENCE.mla


def _trinity_share():
    """trinity_mini's: top-8, scale 2.826, behind a local gated attention
    layer with per-head q/k norms (``benchmark/reference/trinity_mini.py``)."""
    reference = _load("reference", "trinity_mini.py")
    net = dict(n_heads=4, n_kv_heads=2, head_dim=8, window=4, memory_len=6, rope_theta=1e4,
               norm_eps=1e-6, routed_scale=2.826, expert_offset=0)
    mixer = hybrid.GroupedQueryAttention(32, 4, 2, 8, 4, 1e4, qk_norm=True, gated=True, eps=1e-6)
    empty = {"k": jnp.zeros((2, 0, 2, 8)), "v": jnp.zeros((2, 0, 2, 8)),
             "n": jnp.zeros((2,), jnp.int32)}
    return reference, net, 8, 2.826, mixer, empty, lambda p, h, observed, net: (
        reference.attention(p, h, observed, True, net))


@pytest.mark.parametrize("family", [_kanana_share, _trinity_share], ids=["kanana", "trinity"])
def test_the_eight_shares_add_up_to_the_uncut_reference(family):
    """Offsets 0, 16 .. 112 of the eight-chip deployment at a small width:
    each share scores and chooses over all 128 experts with the whole router
    (the same choices) and adds its own 16 experts' terms; the eight routed
    terms, with the shared expert and the attention mixer counted once, add up
    to the reference's layer whose 128 experts are on one chip.  Both
    configurations that stand for that deployment: each its own ``top_k``,
    scale, mixer and reference."""
    reference, base, k, scale, mixer, empty, attention = family()
    d, experts, held, width, shared = 32, 128, 16, 16, 24
    net = dict(base, n_experts=experts, top_k=k, experts_held=experts, expert_width=width,
               shared_width=shared)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2, 9, d))
    observed = jnp.ones((2, 9), jnp.float32)
    attend = Layer(mixer, 1e-6)
    p_l = attend.init(jax.random.fold_in(key, 1), x, empty, observed > 0)["params"]
    whole = {
        "router": 3 * jax.random.normal(jax.random.fold_in(key, 2), (d, experts)),
        "score_bias": 0.03 * jax.random.normal(jax.random.fold_in(key, 3), (experts,)),
        "w1": jax.random.normal(jax.random.fold_in(key, 4), (experts, d, 2 * width)) / 6,
        "w2": jax.random.normal(jax.random.fold_in(key, 5), (experts, width, d)) / 4,
        "shared_up": {"kernel": jax.random.normal(jax.random.fold_in(key, 6), (d, 2 * shared)) / 6},
        "shared_down": {"kernel": jax.random.normal(jax.random.fold_in(key, 8), (shared, d)) / 5},
    }
    norm = 1.0 + 0.3 * jax.random.normal(jax.random.fold_in(key, 9), (d,))
    with jax.default_matmul_precision("highest"):
        x1 = x + attention(p_l["mixer"], reference.rms_norm(x, p_l["norm"], 1e-6), observed, net)
        h = reference.rms_norm(x1, norm, 1e-6)
        routed_and_shared, chosen = reference.experts(whole, h, net)
        want = x1 + routed_and_shared
        # every share is given rows
        assert len(np.unique(chosen)) > 16 and len(np.unique(np.asarray(chosen) // held)) == 8

        got, _, _, _ = jax.jit(lambda p: attend.apply({"params": p}, x, empty, observed > 0))(p_l)
        np.testing.assert_allclose(got, x1, atol=1e-5)          # the mixer, once
        rows = 0
        for offset in range(0, experts, held):
            own = dict(whole, w1=whole["w1"][offset:offset + held],
                       w2=whole["w2"][offset:offset + held])
            if offset:      # what every chip computes alike is counted once
                own = {k: v for k, v in own.items() if not k.startswith("shared")}
            layer = ExpertLayer(d, experts, k, width, 0 if offset else shared, scale, held, offset,
                                "sigmoid", True)
            out, picked, counts, _ = jax.jit(lambda p: layer.apply({"params": p}, h))(own)
            np.testing.assert_array_equal(np.sort(picked, axis=-1), np.sort(chosen, axis=-1))
            rows += int(counts["rows"].sum())
            got = got + out
    assert rows == chosen.size
    np.testing.assert_allclose(got, want, atol=2e-5)


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
    if fault == "half_split_on_one_side":
        monkeypatch.setattr(hybrid, "_rope_pairs", _half_split_queries)
    else:
        monkeypatch.setattr(REFERENCE, *REFERENCE_FAULTS[fault])
    got = _window(module, params, obs, mask)
    want = _reference(params, obs, mask, _config(), choices=got["choices"])
    assert _apart(got, want, mask) > 5 * F32_TOLERANCE


def test_the_eight_bit_control_fails_where_bfloat16_holds(toy):
    """bfloat16 weights and stream hold to the reference forced to their
    choices; weights rounded leaf by leaf to float8 e4m3 first do not."""
    module, _, obs, mask, _ = toy
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731
    sound, rough = [], []
    # one traced forward and one traced reference for the six readings
    forward = jax.jit(lambda w: module.apply(
        {"params": w}, to(obs, jnp.bfloat16), None, seq=True, key_mask=mask))
    reference = jax.jit(lambda p, choices: REFERENCE.forward(
        p, obs, mask, _config(), choices=choices))
    for seed in range(3):
        p = _init(module, seed)
        for weights, readings in ((to(p, jnp.bfloat16), sound),
                                  (to(to(p, jnp.float8_e4m3fn), jnp.bfloat16), rough)):
            got = forward(weights)
            with jax.default_matmul_precision("highest"):
                want = reference(p, got["choices"])
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
    assert isinstance(module, HybridNet) and module.with_return and module.pattern == "L-LELE"
    params = traffic.seeded_params(module, env, 1)
    batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
    assert 0.2 < float(np.mean(batch["observation_mask"])) < 0.8
    return config, args, module, params, batch


def test_the_scan_path_and_the_window_path_are_the_reference_on_geister(geister):
    """``forward_prediction`` through ``env.net()``: the whole-window call and
    the train step's scan over step mode, burn-in 4, against ``forward_rows``."""
    config, args, module, params, batch = geister
    predict = lambda seq: jax.jit(lambda p, b: forward_prediction(  # noqa: E731
        module, p, b, dict(args, seq_forward=seq)))(params, batch)
    with jax.default_matmul_precision("highest"):
        window, scan = predict(True), predict(False)
        want = jax.jit(lambda p, b, c: REFERENCE.forward_rows(p, b, config, 4, choices=c))(
            params, batch, window["choices"])
    observed = batch["observation_mask"][:, 4:]
    legal = (batch["action_mask"][:, 4:] == 0) & (batch["turn_mask"][:, 4:] > 0)
    for head in HEADS:
        keep = legal if head == "policy" else observed > 0
        for got in (window, scan):
            diff = np.where(keep, np.asarray(got[head]) - np.asarray(want[head]) * (
                1 if head == "policy" else observed), 0.0)
            assert float(np.abs(diff).max()) < 1e-4, head


def test_a_train_step_moves_every_new_part_and_a_checkpoint_brings_it_back(geister, tmp_path):
    """One ``TrainContext`` update under ``remat: block``: finite, the four
    projections, the latent's norm, the dense layer, every router, the shared
    expert and the experts move, the step counts its latents; the state saved
    and loaded is the state; the layout says what the kind added; and the
    step's cache key knows the new scopes."""
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
    assert metrics["counter_rows_held"] > 0
    assert metrics["counter_latent_state_values"] > 0
    assert metrics["counter_latent_state_values"] / metrics["counter_expanded_state_values"] == (
        pytest.approx(SLOT / (4 * 18)))
    moved = lambda *path: not np.allclose(  # noqa: E731
        np.asarray(_at(after, path)), np.asarray(_at(before, path)))
    for path in (("layer0", "mixer", "q", "kernel"), ("layer0", "mixer", "kv_a", "kernel"),
                 ("layer2", "mixer", "kv_norm"), ("layer2", "mixer", "kv_b"),
                 ("layer4", "mixer", "o", "kernel"), ("layer1", "mixer", "gate", "kernel"),
                 ("layer3", "mixer", "router"), ("layer5", "mixer", "shared_up", "kernel"),
                 ("layer3", "mixer", "w1"), ("layer5", "mixer", "w2")):
        assert moved(*path), path
    assert not moved("layer3", "mixer", "score_bias")

    checkpoint.save_train_state(str(tmp_path / "state.ckpt"), state)
    loaded = checkpoint.load_train_state(str(tmp_path / "state.ckpt"), jax.device_get(state))
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(jax.device_get(state))):
        np.testing.assert_array_equal(a, b)

    records = trace.read_trace(str(tmp_path / "trace.jsonl"))
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


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


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
