"""HybridNet as a looped dense trunk (models/hybrid.py: ``*-`` layers with
sandwich norms and rotary positions, the stack run ``loops`` times over its
own weights, state per application) at tiny widths on the CPU, against the
plain reference of the configuration it was written for
(benchmark/reference/ouro_2_6b.py, which imports nothing from
handyrl_tpu.models), through ``forward_prediction`` and the train step.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import HybridNet
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import PACKED_ORDER, forward_prediction, pack_order
from nets import HEADS, REPO, SCAN, _apart, _load, _predict, _random_window, _scan, _window

NET = dict(
    pattern="*-*-", loops=4, sandwich=True, d_model=64, norm_eps=1e-6,
    n_heads=4, n_kv_heads=4, head_dim=16, rope_theta=1e6, mlp_width=176, memory_len=200,
)


def _moved(params, seed):
    """Every norm scale and bias moved off its initial 1 or 0, so that a
    dropped or misplaced one shows."""
    rng = np.random.RandomState(seed)
    return jax.tree.map(
        lambda x: x + 0.2 * jnp.asarray(rng.randn(*x.shape), x.dtype) if x.ndim == 1 else x, params)


OURO = nets.Family("tiny_ouro", NET, "ouro_2_6b.py", lively=_moved, actions=5)
REFERENCE = OURO.REFERENCE
FLOPS = _load("flops", "ouro.py")
_net, _geister, _reference = (
    functools.partial(f, OURO) for f in (nets._module, nets._geister, nets._reference))


def _params(module, obs, seed=0):
    return _moved(nets._params(module, obs, seed), seed)


# -- the system against the plain reference --------------------------------


@pytest.mark.parametrize("pattern,loops", [("*-", 1), ("*-*-", 1), ("*-", 4), ("*-*-", 4)])
def test_window_matches_the_reference_in_float32(pattern, loops):
    module = _net(pattern=pattern, loops=loops)
    obs, mask = _random_window(1)
    params = _params(module, obs)
    assert ("exit_gate" in params) == (loops > 1)
    got = _window(module, params, obs, mask)
    want = _reference(params, obs, mask, pattern=pattern, loops=loops)
    assert _apart(got, want, mask) < 1e-5
    assert ("layer_applications" in got["counters"]) == (loops > 1)
    if loops > 1:
        assert float(got["counters"]["layer_applications"]) == loops * len(pattern)
        stay = float((want["exit"][..., -1] * mask).sum() / mask.sum())
        assert float(got["counters"]["exit_mass_last"]) == pytest.approx(stay, abs=1e-5)
        np.testing.assert_allclose(want["exit"].sum(axis=-1), 1.0, atol=1e-6)   # a distribution


@pytest.mark.parametrize("kind", ["*", "-"])
def test_one_sub_layer_is_the_references(kind):
    """``x + RMSNorm(mixer(RMSNorm(x)))`` for each mixer alone, against the
    reference's own functions, and by hand for the rotation."""
    module = _net(pattern=kind, loops=1)
    obs, mask = _random_window(2)
    params = _params(module, obs)
    net, eps = dict(NET, pattern=kind, loops=1), NET["norm_eps"]
    dense = lambda p, x: x @ p["kernel"] + p["bias"]  # noqa: E731
    x = dense(params["enc2"], jnp.maximum(dense(params["enc1"], obs["a"]), 0.0))
    p = params["layer0"]
    inner = REFERENCE.rms_norm(x, p["norm"], eps)
    mixed = (REFERENCE.attention(p["mixer"], inner, mask, net) if kind == "*"
             else REFERENCE.gated_mlp(p["mixer"], inner))
    h = REFERENCE.rms_norm(x + REFERENCE.rms_norm(mixed, p["norm_out"], eps), params["norm_f"], eps)
    want = {"policy": dense(params["policy"], h), "value": jnp.tanh(dense(params["value"], h)),
            "return": dense(params["return_head"], h)}
    got = _window(module, params, obs, mask)
    assert _apart(got, want, mask) < 1e-5


def test_the_rotation_by_hand():
    """theta 1e6 over all 16 dims, pairs (d, d + 8), angle p * theta^(-2d/16)."""
    from handyrl_tpu.models.hybrid import _rope

    rng = np.random.RandomState(3)
    x = rng.randn(2, 5, 3, 16).astype(np.float32)
    pos = np.array([[0, 1, 2, 3, 4], [7, 7, 9, 50, 91]], np.float32)
    want = np.empty_like(x)
    for d in range(8):
        angle = pos * 1e6 ** (-2 * d / 16)
        cos, sin = np.cos(angle)[..., None], np.sin(angle)[..., None]
        want[..., d] = x[..., d] * cos - x[..., d + 8] * sin
        want[..., d + 8] = x[..., d + 8] * cos + x[..., d] * sin
    np.testing.assert_allclose(_rope(jnp.asarray(x), jnp.asarray(pos), 1e6), want, atol=1e-5)
    np.testing.assert_allclose(REFERENCE.rope(jnp.asarray(x), jnp.asarray(pos), 1e6), want, atol=1e-5)
    # a position is what is rotated by: the scores depend on the difference alone
    q, k = jnp.asarray(x[:, :1]), jnp.asarray(x[:, 1:2])
    score = lambda a, b: float(jnp.sum(_rope(q, jnp.full((2, 1), a), 1e6)  # noqa: E731
                                       * _rope(k, jnp.full((2, 1), b), 1e6)))
    assert score(5.0, 2.0) == pytest.approx(score(40.0, 37.0), rel=1e-4)
    assert score(5.0, 2.0) != pytest.approx(score(5.0, 3.0), rel=1e-4)


BF16_TOLERANCE = 0.12    # of the head's scale, at width 64 through 16 applications


def test_window_matches_the_reference_in_bfloat16_at_a_stated_tolerance():
    """The train step's forward: a bf16 copy of the weights, bf16 activations
    and residual stream, norms, rotations and softmax in float32.  Sound
    seeds read 0.029 to 0.060 of the scale, the same forward from 8-bit
    weights 0.33 to 0.42: the tolerance lies between, twice over the one and
    under a third of the other."""
    module = _net()
    to = lambda tree, dtype: jax.tree.map(lambda x: x.astype(dtype), tree)  # noqa: E731
    sound, rough = [], []
    for seed in range(3):
        obs, mask = _random_window(10 + seed, rows=4, steps=12)
        params = _params(module, obs, seed)
        want = _reference(params, obs, mask)
        eight = to(to(params, jnp.float8_e4m3fn), jnp.bfloat16)
        for weights, readings in ((to(params, jnp.bfloat16), sound), (eight, rough)):
            got = _window(module, weights, to(obs, jnp.bfloat16), mask)
            readings.append(_apart(got, want, mask))
    assert max(sound) < BF16_TOLERANCE < min(rough), (sound, rough)


# -- the faults the whole-net comparison must tell ---------------------------


def test_three_faults_each_fail_the_whole_net_comparison():
    module = _net()
    obs, mask = _random_window(4, rows=4, steps=12, observed=0.5)
    assert 0 < float(mask.sum()) < mask.size and float(mask[:, 0].min()) == 0
    params = _params(module, obs)
    want = _reference(params, obs, mask)
    window = lambda net: _window(net, params, obs, mask)  # noqa: E731
    assert _apart(window(module), want, mask) < 1e-5
    assert _apart(_scan(module, params, obs, mask)[0], want, mask) < 1e-5
    faults = {
        "three passes": window(_net(loops=3)),
        "no second norm": window(_net(sandwich=False)),
        "positions over all steps": _scan(module, params, obs, mask, count_every_step=True)[0],
    }
    for name, got in faults.items():
        assert _apart(got, want, mask) > 1e-3, name
    # and one layer's second norm alone: its scale at 1 where the weights' is not
    flat = dict(params, layer2=dict(params["layer2"], norm_out=jnp.ones_like(params["layer2"]["norm_out"])))
    assert _apart(_window(module, flat, obs, mask), want, mask) > 1e-3


# -- state per application -------------------------------------------------


def test_ring_t_i_is_written_by_application_t_i_only():
    module = _net(memory_len=4)
    obs, _ = _random_window(5, rows=2, steps=3)
    params = _params(module, obs)
    hidden = module.initial_state((2,))
    kinds = NET["pattern"] * NET["loops"]
    assert len(hidden["layers"]) == 16 and module.layout()["applications"] == 16
    assert [sorted(s) for s in hidden["layers"]] == [["k", "v"] if k == "*" else [] for k in kinds]
    first = module.apply({"params": params}, jax.tree.map(lambda x: x[:, 0], obs), hidden)["hidden"]
    second = module.apply({"params": params}, jax.tree.map(lambda x: x[:, 1], obs), first)["hidden"]
    rings = [i for i, kind in enumerate(kinds) if kind == "*"]
    for i in rings:
        k1, k2 = np.asarray(first["layers"][i]["k"]), np.asarray(second["layers"][i]["k"])
        assert np.abs(k1[:, 0]).min() > 0 and not k1[:, 1:].any()        # slot 0 alone
        assert np.array_equal(k2[:, 0], k1[:, 0]) and np.abs(k2[:, 1]).min() > 0 and not k2[:, 2:].any()
    # the same layer's rings of different passes hold different keys: each
    # application projects its own input
    same_layer = [i for i in rings if i % 4 == 0]
    assert len(same_layer) == 4
    for a in same_layer:
        for b in same_layer:
            if a < b:
                assert not np.allclose(first["layers"][a]["k"], first["layers"][b]["k"], atol=1e-4)
    # application (t, i) reads ring (t, i): a key of another pass in its place moves the output
    swapped = list(first["layers"])
    swapped[0], swapped[4] = swapped[4], swapped[0]
    step = lambda h: module.apply({"params": params}, jax.tree.map(lambda x: x[:, 1], obs), h)  # noqa: E731
    assert not np.allclose(step(first)["policy"], step(dict(first, layers=tuple(swapped)))["policy"],
                           atol=1e-5)


def test_step_mode_acts_through_the_inference_model():
    from handyrl_tpu.models import InferenceModel, init_variables

    env = make_env({"env": "TicTacToe", "net": "hybrid", "net_args": dict(NET, memory_len=4)})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env))
    env.reset()
    hidden = model.init_hidden()
    assert len(hidden["layers"]) == 16
    first = model.inference(env.observation(0), hidden)
    assert first["policy"].shape == (9,) and float(first["hidden"]["pos"]) == 1.0
    assert set(first) == {"policy", "value", "hidden"}      # no output of the gate's
    env.play(4)
    again = model.inference(env.observation(0), first["hidden"])
    fresh = model.inference(env.observation(0), hidden)
    assert not np.allclose(again["policy"], fresh["policy"], atol=1e-5)   # the state matters


def test_a_routed_layer_in_a_looped_stack_is_refused_by_name():
    obs, _ = _random_window(0)
    module = HybridNet(num_actions=3, pattern="E*", loops=2)
    with pytest.raises(ValueError, match="a routed layer is run once"):
        module.init(jax.random.PRNGKey(0), jax.tree.map(lambda x: x[:, 0], obs), None)


def test_defaults_build_the_tower_they_always_did():
    """No loop, no second norm, no rotation unless asked: the parameters and
    the counters of a net that names none of the new fields are the old ones."""
    module = HybridNet(num_actions=3, pattern="*M", d_model=32)
    obs, mask = _random_window(6)
    params = module.init(jax.random.PRNGKey(0), jax.tree.map(lambda x: x[:, 0], obs), None)["params"]
    assert set(params) == {"enc1", "enc2", "layer0", "layer1", "norm_f", "policy", "value"}
    assert set(params["layer0"]) == {"norm", "mixer"}
    out = _window(module, params, obs, mask)
    assert set(out["counters"]) == {"packed_slots", "observed_steps", "packed_dropped"}
    assert len(module.initial_state((1,))["layers"]) == 2


def test_the_second_norms_scale_starts_where_it_is_told():
    """``out_scale_init`` is an initial value and nothing else: the forward
    of given parameters is the same net's whatever it says."""
    obs, mask = _random_window(9)
    plain, small = _net(), _net(out_scale_init=0.5)
    init = lambda net: net.init(jax.random.PRNGKey(0), jax.tree.map(lambda x: x[:, 0], obs), None)["params"]  # noqa: E731
    ones, halves = init(plain), init(small)
    for name in ("layer0", "layer1", "layer2", "layer3"):
        assert np.array_equal(ones[name]["norm_out"], np.ones(64))
        assert np.array_equal(halves[name]["norm_out"], np.full(64, 0.5))
        assert np.array_equal(halves[name]["norm"], np.ones(64))
    assert np.array_equal(halves["norm_f"], np.ones(64))
    a, b = _window(plain, halves, obs, mask), _window(small, halves, obs, mask)
    assert np.array_equal(a["policy"], b["policy"])
    assert _apart(b, _reference(halves, obs, mask), mask) < 1e-5
    # nearer the identity: the branches move the encoder's output less
    moved = lambda net, params: float(jnp.abs(  # noqa: E731
        _window(net, params, obs, mask)["policy"]
        - _window(_net(loops=1, pattern=""), params, obs, mask)["policy"]).mean())
    assert moved(small, halves) < moved(plain, ones)


def test_layout_counts_the_parameters_by_kind_and_the_applications():
    module = _net()
    obs, _ = _random_window(7)
    params = _params(module, obs)
    layout = module.layout()
    assert (layout["pattern"], layout["loops"], layout["applications"]) == ("*-*-", 4, 16)
    size = lambda *names: sum(x.size for n in names for x in jax.tree.leaves(params[n]))  # noqa: E731
    assert layout["params_attention"] == size("layer0", "layer2") == 2 * (2 * 64 + 4 * 64 * 64)
    assert layout["params_mlp"] == size("layer1", "layer3") == 2 * (2 * 64 + 3 * 64 * 176)
    assert layout["params_mamba"] == layout["params_experts"] == 0


# -- through forward_prediction and the train step ----------------------------


@pytest.fixture(scope="module")
def geister():
    config, args, module, params, batch = nets._geister_windows(
        OURO, batch_size=2, burn_in_steps=8, forward_steps=10)
    assert module.loops == 4
    return config, args, module, _moved(params, 1), batch


def _with_order(batch, burn_in, bounds):
    seen = np.moveaxis(np.asarray(batch["observation_mask"])[..., 0] > 0, 1, 2)     # (B, P, T)
    parts = {"burn_in": seen[..., :burn_in], "forward": seen[..., burn_in:]}
    return dict(batch, **{PACKED_ORDER: {
        name: pack_order(parts[name], bound) for name, bound in bounds.items()}})


@pytest.mark.parametrize("burn_in", [0, 8])
def test_whole_window_matches_the_scan_path_and_the_reference(geister, burn_in):
    config, args, module, params, batch = geister
    args = dict(args, burn_in_steps=burn_in)
    seen = np.asarray(batch["observation_mask"])[:, burn_in:] > 0
    most = int(np.moveaxis(seen[..., 0], 1, 2).sum(axis=-1).max())
    bounds = dict({"forward": most}, **({"burn_in": 8} if burn_in else {}))
    window = _predict(module, args)
    whole, packed = window(params, batch), window(params, _with_order(batch, burn_in, bounds))
    scan = _predict(module, args, **SCAN)(params, batch)
    want = jax.jit(lambda p, b: REFERENCE.forward_rows(p, b, config, burn_in))(params, batch)
    for head in ("value", "return"):
        np.testing.assert_allclose(whole[head], scan[head], atol=2e-5)
        np.testing.assert_allclose(packed[head], scan[head], atol=2e-5)
        np.testing.assert_allclose(whole[head], want[head] * seen, atol=2e-5)
    legal = (batch["action_mask"][:, burn_in:] == 0) & (batch["turn_mask"][:, burn_in:] > 0)
    for got in (whole, packed, scan):
        np.testing.assert_allclose(np.where(legal, got["policy"], 0.0),
                                   np.where(legal, want["policy"], 0.0), atol=2e-5)
    assert "choices" not in whole and "counters" not in scan
    # the packed array is the bound's size, and holds every token
    rows = batch["action"].shape[0] * batch["action"].shape[2]
    assert float(packed["counters"]["packed_slots"]) == rows * sum(bounds.values())
    assert float(packed["counters"]["packed_dropped"]) == 0
    assert float(packed["counters"]["exit_mass_last"]) == pytest.approx(
        float(whole["counters"]["exit_mass_last"]), abs=1e-5)


def _loss(module, args, batch):
    def loss(p, **over):
        out = forward_prediction(module, p, batch, dict(args, **over))
        seen = batch["observation_mask"][:, args["burn_in_steps"]:]
        return sum(jnp.sum(jnp.square(out[k] * seen)) for k in ("value", "return"))
    return loss


_GRADS = {}


def _grad(module, args, batch, **over):
    """Jitted params -> (loss, its gradient) of ``_loss`` under ``args`` with
    ``over``: one program a (net, burn-in steps, ``over``), whichever case
    asks (every case hands the fixture's ``args`` and ``batch``)."""
    loss = _loss(module, args, batch)
    key = (module, args["burn_in_steps"], tuple(sorted(over.items())))
    return _GRADS.setdefault(key, jax.jit(jax.value_and_grad(lambda p: loss(p, **over))))


def _close(got, want, rel):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=rel * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))


def test_the_gradient_of_a_shared_weight_sums_its_four_uses(geister):
    """Without burn-in: the window path's gradient, the scan path's and the
    reference's (plain jax.grad through its double loop) are one; the gate
    is read by no loss."""
    config, args, module, params, batch = geister
    args = dict(args, burn_in_steps=0)
    def reference(p):
        out = REFERENCE.forward_rows(p, batch, config, 0)
        return sum(jnp.sum(jnp.square(out[k] * batch["observation_mask"]))
                   for k in ("value", "return"))

    window = _grad(module, args, batch)(params)[1]
    scan = _grad(module, args, batch, **SCAN)(params)[1]
    want = jax.jit(jax.grad(reference))(params)
    _close(window, want, 2e-4)
    _close(scan, want, 2e-4)
    assert float(jnp.abs(want["layer1"]["mixer"]["up"]["kernel"]).max()) > 1e-3
    assert not jax.tree.leaves(jax.tree.map(lambda g: bool(jnp.any(g != 0)), window["exit_gate"]))[0]
    # four uses: the gradient of one pass alone is another
    single = HybridNet(num_actions=module.num_actions, with_return=True, **dict(NET, loops=1))
    one = _grad(single, args, batch)({k: v for k, v in params.items() if k != "exit_gate"})[1]
    assert not np.allclose(one["layer1"]["mixer"]["up"]["kernel"],
                           window["layer1"]["mixer"]["up"]["kernel"], atol=1e-4)


def test_burn_in_hands_no_gradient_and_remat_changes_nothing(geister):
    """The scan path's burn-in rule: what application (t, i) leaves of the
    burn-in steps carries no gradient, so the window path's parameter
    gradient equals the scan's; and a checkpoint per application replays
    what it dropped."""
    _, args, module, params, batch = geister
    assert args["burn_in_steps"] == 8
    value, window = _grad(module, args, batch)(params)
    scan = _grad(module, args, batch, **SCAN)(params)[1]
    _close(window, scan, 5e-4)
    again, block = _grad(module, args, batch, remat="block")(params)
    assert float(again) == pytest.approx(float(value), rel=1e-6)
    _close(block, window, 1e-5)
    # with the hand-off's gradient let through, the gradient is another
    through = _grad(module, dict(args, burn_in_steps=0), batch)(params)[1]
    assert float(jnp.abs(window["enc1"]["kernel"]).sum()) < float(jnp.abs(through["enc1"]["kernel"]).sum())


def test_train_step_packs_counts_and_records_its_layout(geister, tmp_path):
    from handyrl_tpu.utils import trace

    from benchmark import traffic

    # windows long enough for the host to find a bound under their length
    _, args, env, module = _geister(
        {"batch_size": 2, "burn_in_steps": 8, "forward_steps": 40, "remat": "block"})
    params = jax.tree.map(lambda x: x, geister[3])
    params["exit_gate"]["bias"] = jnp.zeros_like(params["exit_gate"]["bias"])
    batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        ctx = TrainContext(module, args, make_mesh({"dp": 1}))
        put = ctx.put_batch(batch)
    finally:
        trace.shutdown()
    records = trace.read_trace(str(tmp_path / "trace.jsonl"))
    layout, = [r["attrs"] for r in records if r["name"] == "model.layout"]
    assert (layout["pattern"], layout["loops"], layout["applications"]) == ("*-*-", 4, 16)
    trunk = sum(x.size for name, sub in params.items() if name.startswith("layer")
                for x in jax.tree.leaves(sub))
    assert layout["params_attention"] + layout["params_mlp"] == trunk
    assert PACKED_ORDER in put and put[PACKED_ORDER]["forward"].shape[-1] == 32
    before = jax.device_get(params)
    state, metrics = ctx.train_step(ctx.init_state(params), put, 1e-3)
    metrics, after = jax.device_get(metrics), jax.device_get(state["params"])
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    assert metrics["counter_layer_applications"] == 16
    assert 0.0 < metrics["counter_exit_mass_last"] < 1.0
    assert metrics["counter_packed_dropped"] == 0
    assert metrics["counter_observed_steps"] == float(np.sum(batch["observation_mask"]))
    # the shared weights moved; the gate, which no loss reads, only by the
    # optimizer's L2 decay: towards zero, and its bias of 0 not at all
    assert not np.allclose(after["layer0"]["mixer"]["q"]["kernel"], before["layer0"]["mixer"]["q"]["kernel"])
    assert (np.abs(after["exit_gate"]["kernel"]) <= np.abs(before["exit_gate"]["kernel"])).all()
    assert not after["exit_gate"]["bias"].any()


# -- the scopes a device profile reads -----------------------------------------


def _op_names(module, params, obs, mask):
    """The ``op_name`` of each op of the compiled forward and backward."""
    import re

    def loss(p):
        out = module.apply({"params": p}, obs, None, seq=True, key_mask=mask, remat="block")
        return sum(jnp.sum(out[k] ** 2) for k in HEADS)

    return re.findall(r'op_name="([^"]*)"', jax.jit(jax.grad(loss)).lower(params).compile().as_text())


def test_the_trunks_phases_are_named_and_nothing_else_bears_the_names(monkeypatch):
    """Forward, replay and backward ops of each phase carry its scope; with
    the constants renamed no ``op_name`` reads as one (no flax module, jax
    primitive or jitted helper of the step has such a name)."""
    from benchmark import trace_reduce
    from handyrl_tpu.models import hybrid

    scopes = (hybrid.ATTN_SCOPE, hybrid.ROPE_SCOPE, hybrid.GQA_SCOPE, hybrid.MLP_SCOPE,
              hybrid.NORM_SCOPE)
    assert scopes == ("attn", "rope", "gqa", "mlp", "norm")
    module = _net()
    obs, mask = _random_window(8)
    params = _params(module, obs)
    names = _op_names(module, params, obs, mask)
    for scope in scopes:
        inside = [n for n in names if trace_reduce.scopes_of(n, (scope,))]
        assert [n for n in inside if "transpose(" in n] and [n for n in inside if "transpose(" not in n], scope
    # rope and gqa lie inside attn; the products of an MLP application under mlp
    for name in names:
        found = trace_reduce.scopes_of(name, scopes)
        if "rope" in found or "gqa" in found:
            assert "attn" in found, name
        if "/gate/" in name or "/up/" in name or "/down/" in name:
            assert "mlp" in found, name
    for constant in ("ATTN_SCOPE", "ROPE_SCOPE", "GQA_SCOPE", "MLP_SCOPE", "NORM_SCOPE"):
        monkeypatch.setattr(hybrid, constant, "renamed_" + constant.lower())
    bare = _op_names(module, params, obs, mask)
    assert not [n for n in bare if trace_reduce.scopes_of(n, scopes)]


# -- the count of its work ----------------------------------------------------


def test_flops_of_the_published_cell_against_a_hand_count():
    with open(os.path.join(REPO, "benchmark", "configs", "ouro_2_6b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "workloads", "ouro_train_t192.json")) as f:
        cell = json.load(f)
    work = FLOPS.train_update(config, cell)
    # parameters, by hand: the issue's arithmetic
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    rest = 270 * 2048 + 2048 + 2048 * 2048 + 2048 + 2048 + 2049 + 2049 * 216
    assert layer == 51_388_416
    assert work["parameters"] == 8 * layer + rest == 416_305_369
    assert work["applications"] == 64
    trained, burn = 64 * 184 * 0.413, 64 * 8 * 0.127
    assert work["tokens"] == pytest.approx(trained + burn)
    # multiply-adds a token: per application, 32 layer applications a forward
    keys = (184 * 0.413 + 8 * 0.127 + 1) / 2
    attn = 4 * 2048 * 2048 + 2 * keys * 16 * 128
    mlp = 3 * 2048 * 5632
    per_token = 270 * 2048 + 2048 * 2048 + 2048 * 216 + 4 * 8 * (attn + mlp)
    assert work["flops"] == pytest.approx(2 * per_token * (3 * trained + burn))
    # 6 x the matrix parameters x tokens would be four times too low
    assert work["flops"] / (6 * 8 * (layer - 4 * 2048) * (trained + burn)) == pytest.approx(4.0, rel=0.02)
    assert 46e12 < work["flops"] < 50e12
    scopes = FLOPS.scope_work(config, cell)
    assert scopes["mlp"]["flops"] == pytest.approx(2 * 32 * mlp * (3 * trained + burn))
    assert scopes["attn"]["flops"] == pytest.approx(2 * 32 * attn * (3 * trained + burn))
    assert scopes["mlp"]["flops"] + scopes["attn"]["flops"] < work["flops"]
    assert scopes["mlp"]["bytes"] / 819e9 < scopes["mlp"]["flops"] / 197e12      # compute-bound
