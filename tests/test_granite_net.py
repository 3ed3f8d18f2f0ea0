"""HybridNet as one period of the ``granitemoehybrid`` family (a mixer and an
expert sub-layer in every layer, each ``x + 0.22 f(RMSNorm(x))``; a softmax
router over the chosen logits; gated experts and shared expert; attention
scores times a given scale; multipliers on the encoder and under the policy
logits; parameters made in ``param_dtype``) at tiny widths on the CPU, against
the plain reference of the configuration it was written for
(benchmark/reference/granite_4_0_h_small.py, which imports nothing from
handyrl_tpu), in both modes, through the train step, the streaming rollout
and the actor host's loop.
"""

import functools
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import HybridNet
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import forward_prediction
from handyrl_tpu.runtime import actor_host, device_rollout
from handyrl_tpu.runtime.device_rollout import build_streaming_fn
from handyrl_tpu.utils import trace
from nets import HEADS, REPO, SCAN, _load, _predict

NET = dict(
    pattern="ME*EME", d_model=32, norm_eps=1e-5,
    mamba_heads=4, mamba_head_dim=16, n_groups=1, state_size=16, conv_kernel=4, chunk=4,
    n_experts=8, top_k=3, expert_width=16, shared_width=32, routed_scale=1.0,
    experts_held=8, expert_offset=0, router="softmax", gated_experts=True,
    n_heads=4, n_kv_heads=2, head_dim=16, memory_len=200,
    attn_score_scale=0.0625, residual_scale=0.22, embed_scale=12.0, logits_divisor=16.0,
)
GRANITE = nets.Family("tiny_granite", NET, "granite_4_0_h_small.py")
REFERENCE = GRANITE.REFERENCE
FLOPS = _load("flops", "granite_moe_hybrid.py")
_config, _geister = (functools.partial(f, GRANITE) for f in (nets._config, nets._geister))


@pytest.fixture(scope="module")
def geister():
    """Windows of 16 steps of random-play Geister games, every player
    observing on its own turns (the scan path unrolls its steps on one CPU
    device, so a longer window is minutes of compile; whole games in step
    mode are the rehearsal's: benchmark/tests/test_granite_rehearsal.py)."""
    return nets._geister_windows(GRANITE, batch_size=3, burn_in_steps=0, forward_steps=16)


@pytest.fixture(scope="module")
def reference_rows(geister):
    """The reference's outputs on the fixture's windows, its own choices."""
    config, _, _, params, batch = geister
    return jax.jit(lambda p, b: REFERENCE.forward_rows(p, b, config, 0))(params, batch)


def _masks(batch):
    legal = (batch["action_mask"] == 0) & (batch["turn_mask"] > 0)
    observed = batch["observation_mask"] > 0
    return {"policy": legal, "value": observed, "return": observed}


def _worst(got, want, batch):
    """Largest absolute difference a head, over legal logits and observed values."""
    return {head: float(np.abs(np.where(mask, np.asarray(got[head], np.float32)
                                        - np.asarray(want[head], np.float32), 0.0)).max())
            for head, mask in _masks(batch).items()}


# -- both modes against the plain reference ---------------------------------


def test_step_mode_is_window_mode_is_the_reference_in_float32(geister, reference_rows):
    config, args, module, params, batch = geister
    window = _predict(module, args)(params, batch)
    steps = _predict(module, args, **SCAN)(params, batch)
    want = reference_rows
    for got in (window, steps):
        assert max(_worst(got, want, batch).values()) < 1e-5
    # the reference chose what the window mode chose, in every routed sub-layer
    assert set(window["choices"]) == set(want["choices"]) == {"layer1", "layer3", "layer5"}
    seen = np.asarray(batch["observation_mask"]) > 0
    for name, chosen in want["choices"].items():
        assert np.array_equal(np.sort(np.where(seen, window["choices"][name], 0), -1),
                              np.sort(np.asarray(chosen), -1))
    # the heads carry signal: logits a sixteenth of a unit-scale head's
    assert 0.05 < float(np.abs(want["policy"]).max()) < 1.0
    assert float(np.abs(want["value"]).max()) > 0.1


# what rounding to bfloat16 (weights held in it, a bfloat16 stream, float32
# state, norms, router and softmaxes) moves on six sub-layers of width 32:
# measured 2e-3 on the policy logits (a sixteenth of the other heads' scale),
# 1.5e-2 on value and return
BF16 = {"policy": 6e-3, "value": 5e-2, "return": 5e-2}


def test_bfloat16_parameters_are_made_so_and_both_modes_hold_to_the_reference(geister):
    config, args, _, _, batch = geister
    from benchmark import traffic

    _, _, env, module = _geister({"batch_size": 3, "burn_in_steps": 0, "forward_steps": 16},
                                 param_dtype="bfloat16")
    params = traffic.seeded_params(module, env, 1)
    assert {x.dtype.name for x in jax.tree.leaves(params)} == {"bfloat16"}
    # no float32 tree precedes it: the initialisers draw in the parameters' type
    made = jax.make_jaxpr(lambda key: module.init(
        key, jax.tree.map(lambda x: x[:1, 0, 0], batch["observation"]),
        module.initial_state((1,)))["params"])(jax.random.PRNGKey(0))
    wide = {v.aval.shape for eqn in made.eqns for v in eqn.outvars
            if getattr(v.aval, "dtype", None) == jnp.float32}
    trunk = {x.shape for name, sub in params.items() if name.startswith("layer")
             for x in jax.tree.leaves(sub) if x.ndim >= 2}
    # (the router's 32 x 8 is read in float32, as its logits are computed; enc1
    # meets float32 observations)
    assert len(trunk) >= 8 and wide & trunk <= {params["layer1"]["mixer"]["router"].shape}
    window = _predict(module, args)(params, batch)
    steps = _predict(module, args, **SCAN)(params, batch)
    widened = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    forced = jax.jit(lambda p, b, c: REFERENCE.forward_rows(p, b, config, 0, choices=c))(
        widened, batch, window["choices"])
    for name, got in (("window", window), ("steps", steps)):
        worst = _worst(got, forced, batch)
        assert all(worst[head] <= BF16[head] for head in HEADS), (name, worst)
        assert worst["value"] > 1e-5        # and it is not the float32 computation


FAULTS = {
    "a dropped sub-layer": dict(pattern="ME*EM"),
    "a missing 0.22": dict(residual_scale=1.0),
    "a sigmoid router": dict(router="sigmoid"),
    "an ungated expert": dict(gated_experts=False),
    "a 1/sqrt(d) score scale": dict(attn_score_scale=0.0),
    "no embedding multiplier": dict(embed_scale=1.0),
    "logits not divided": dict(logits_divisor=1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_net_with_one_thing_left_out_fails_the_comparison(geister, reference_rows, fault):
    """The same parameters (where the shapes allow: an ungated expert reads
    the first half of each fused input matrix) through a net that lacks one
    piece of the family's mathematics: past the float32 tolerance the sound
    net holds."""
    config, args, module, params, batch = geister
    want = reference_rows
    other = HybridNet(num_actions=module.num_actions, with_return=True, **dict(NET, **FAULTS[fault]))
    p = params
    if fault == "a dropped sub-layer":
        p = {k: v for k, v in params.items() if k != "layer5"}
    if fault == "a sigmoid router":
        p = {k: (dict(v, mixer=dict(v["mixer"], score_bias=jnp.zeros((8,))))
                 if k.startswith("layer") and "router" in v["mixer"] else v)
             for k, v in params.items()}
    if fault == "an ungated expert":
        p = {k: (dict(v, mixer=dict(
            v["mixer"], w1=v["mixer"]["w1"][..., :16],
            shared_up={"kernel": v["mixer"]["shared_up"]["kernel"][:, :32]}))
            if k.startswith("layer") and "router" in v["mixer"] else v) for k, v in params.items()}
    got = _predict(other, args)(p, batch)
    worst = _worst(got, want, batch)
    assert any(worst[head] > 1e-4 for head in HEADS), worst     # ten times what the sound net reads


# -- one chip's share of a layer ---------------------------------------------


def test_the_two_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Two chips share a layer: experts 0-3 on one, 4-7 on the other, router
    and shared expert whole on both.  The parts the two give, the shared
    expert counted once, are what the uncut reference gives."""
    config = _config(pattern="E", experts_held=8)
    whole = HybridNet(num_actions=5, **config["env_args"]["net_args"])
    rng = np.random.RandomState(3)
    obs = {"a": jnp.asarray(rng.randn(2, 9, 7), jnp.float32)}
    mask = jnp.ones((2, 9), jnp.float32)
    params = whole.init(jax.random.PRNGKey(2), jax.tree.map(lambda x: x[:, 0], obs), None)["params"]
    mixer = params["layer0"]["mixer"]

    def layer_output(net_args, mixer_params):
        """The expert sub-layer's branch alone: (routed + shared)(RMSNorm(x0))."""
        from handyrl_tpu.models.hybrid import ExpertLayer

        a = net_args
        layer = ExpertLayer(a["d_model"], a["n_experts"], a["top_k"], a["expert_width"],
                            a["shared_width"], a["routed_scale"], a["experts_held"],
                            a["expert_offset"], a["router"], a["gated_experts"])
        x0 = REFERENCE.encode(params, obs, a)
        h = REFERENCE.rms_norm(x0, params["layer0"]["norm"], a["norm_eps"])
        return layer.apply({"params": mixer_params}, h)[0], h

    parts = []
    for offset in (0, 4):
        net = dict(config["env_args"]["net_args"], experts_held=4, expert_offset=offset)
        share = dict(mixer, w1=mixer["w1"][offset:offset + 4], w2=mixer["w2"][offset:offset + 4])
        out, h = layer_output(net, share)
        parts.append(out)
        # each share is what the reference gives for that share
        want, _ = REFERENCE.experts(share, h, net)
        np.testing.assert_allclose(out, want, atol=1e-5)
    shared = REFERENCE.gated(h @ mixer["shared_up"]["kernel"]) @ mixer["shared_down"]["kernel"]
    uncut, _ = REFERENCE.experts(mixer, h, config["env_args"]["net_args"])
    np.testing.assert_allclose(parts[0] + parts[1] - shared, uncut, atol=1e-5)
    assert float(jnp.abs(uncut - shared).max()) > 1e-2      # the routed part is not nothing
    # and through the whole net: the uncut net is the uncut reference
    got = whole.apply({"params": params}, obs, None, seq=True, key_mask=mask)
    want = REFERENCE.forward(params, obs, mask, config)
    np.testing.assert_allclose(got["policy"], want["policy"], atol=1e-5)


def test_the_router_is_a_softmax_over_the_chosen_logits():
    from handyrl_tpu.ops.routed_experts import choose

    logits = jnp.asarray(np.random.RandomState(0).randn(6, 8) * 3, jnp.float32)
    chosen, gates = choose(jax.nn.softmax(logits, -1), jnp.zeros((8,)), 3, 1.0)
    top = jnp.argsort(-logits, -1)[:, :3]
    assert np.array_equal(np.sort(chosen, -1), np.sort(top, -1))
    want = jax.nn.softmax(jnp.take_along_axis(logits, chosen, -1), -1)
    np.testing.assert_allclose(gates, want, atol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 1.0, atol=1e-6)


# -- the train step ------------------------------------------------------------


def test_the_train_steps_loss_and_gradients_are_the_references(geister):
    """The window path's loss over the heads and its gradients are the
    reference's (plain ``jax.grad`` through its loops), and one
    ``TrainContext`` step on the same windows is finite and moves the weights."""
    config, args, module, params, batch = geister

    def loss_of(forward):
        def loss(p):
            out = forward(p)
            return sum(jnp.sum(jnp.square(out[k] * batch["observation_mask"]))
                       for k in ("value", "return"))
        return loss

    window = loss_of(lambda p: forward_prediction(module, p, batch, args))
    reference = loss_of(lambda p: REFERENCE.forward_rows(p, batch, config, 0))
    (want_loss, want), (got_loss, got) = (
        jax.jit(jax.value_and_grad(f))(params) for f in (reference, window))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=2e-4 * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))
    assert float(jnp.abs(want["layer1"]["mixer"]["w1"]).max()) > 1e-4

    ctx = TrainContext(module, dict(args, seq_forward=True), make_mesh({"dp": 1}))
    before = jax.device_get(params)
    state, metrics = ctx.train_step(ctx.init_state(params), ctx.put_batch(batch), 1e-3)
    metrics, after = jax.device_get(metrics), jax.device_get(state["params"])
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    assert metrics["counter_rows_held"] > 0 and metrics["counter_buffer_slots"] > 0
    assert not np.allclose(after["layer1"]["mixer"]["w1"], before["layer1"]["mixer"]["w1"])
    assert not np.allclose(after["layer0"]["mixer"]["in_proj"]["kernel"],
                           before["layer0"]["mixer"]["in_proj"]["kernel"])


def test_the_layout_says_what_the_family_added(tmp_path):
    _, args, _, module = _geister({"batch_size": 2, "burn_in_steps": 0, "forward_steps": 8},
                                  param_dtype="bfloat16")
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        TrainContext(module, args, make_mesh({"dp": 1}))
    finally:
        trace.shutdown()
    layout, = [r["attrs"] for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
               if r["name"] == "model.layout"]
    assert (layout["residual_scale"], layout["router"], layout["param_dtype"]) == (
        0.22, "softmax", "bfloat16")
    env = make_env(args["env"])
    from benchmark import traffic

    params = traffic.seeded_params(module, env, 1)
    trunk = sum(x.size for name, sub in params.items() if name.startswith("layer")
                for x in jax.tree.leaves(sub))
    assert layout["params_mamba"] + layout["params_attention"] + layout["params_experts"] == trunk
    # the benchmark's count from shapes is the module's own, sub-layer by sub-layer
    net = dict(NET, param_dtype="bfloat16")
    assert sum(FLOPS.sublayer_parameters(net, kind) for kind in net["pattern"]) == trunk


def test_the_published_period_holds_4_57e9_parameters_all_bfloat16():
    """``jax.eval_shape`` of the module's own ``init`` at the configuration's
    widths: the count the flops file derives from shapes, 9.14 GB."""
    with open(os.path.join(REPO, "benchmark", "configs", "granite_4_0_h_small.json")) as f:
        config = json.load(f)
    env = make_env(config["env_args"])
    module = env.net()
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    shapes = jax.eval_shape(lambda key: module.init(key, obs, module.initial_state((1,)))["params"],
                            jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert {x.dtype.name for x in leaves} == {"bfloat16"}
    assert sum(x.size for x in leaves) == FLOPS.parameters(config) == 4_570_467_160
    hidden = jax.eval_shape(lambda: module.initial_state((1,)))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(hidden)) == \
        FLOPS.state_bytes_per_row(config["env_args"]["net_args"]) + 4      # and ``pos``


# -- the streaming rollout and the actor's loop ---------------------------------


@pytest.fixture(scope="module")
def rollout():
    """Three dispatches of 4 Geister lanes x 8 steps with the tiny net in
    bfloat16, counters on."""
    _, args, env, module = _geister(
        {"batch_size": 2, "burn_in_steps": 0, "forward_steps": 8}, param_dtype="bfloat16",
        experts_held=4)
    from benchmark import traffic

    params = traffic.seeded_params(module, env, 1)
    venv = env.vector_env()
    fn = build_streaming_fn(venv, module, 4, 8, use_observe_mask=False, counters=True)
    plain = build_streaming_fn(venv, module, 4, 8, use_observe_mask=False)
    state, hidden = venv.init(4, jax.random.PRNGKey(0)), module.initial_state((4, 2))
    key = jax.random.PRNGKey(5)
    lowered = fn.lower(params, state, hidden, key)
    outs = []
    for i in range(3):
        state, hidden, records, counted = fn(params, state, hidden, jax.random.fold_in(key, i))
        outs.append((jax.device_get(records), jax.device_get(counted)))
    return module, params, venv, plain, lowered, outs


def test_the_rollout_counts_what_the_step_mode_sowed_and_keeps_its_records(rollout):
    module, params, venv, plain, _, outs = rollout
    for records, counted in outs:
        assert set(counted) == {"rows_held", "buffer_slots", "slots_run", "rows_applied"}
        # one player a lane observes: the net is applied to the 4 acting rows of 8
        assert counted["rows_applied"] == 8 * 4
        # three routed sub-layers, 8 steps, one buffer of blocks x block slots each:
        # 12 pairs over 8 experts in bfloat16 lie in blocks of 16
        from handyrl_tpu.ops.routed_experts import block_rows, row_buffer

        block = block_rows(4, 3, 8, jnp.bfloat16)
        blocks = row_buffer(4, 3, 4, 8, block)[0]
        assert counted["buffer_slots"] == 3 * 8 * blocks * block == 3 * 8 * 5 * 16
        assert counted["slots_run"] == counted["buffer_slots"]      # blocks of 16: all are run
        # every row the net is applied to chooses 3 of 8, 4 held: at most 3 a row
        assert 0 < counted["rows_held"] <= 3 * 8 * 4 * 3
        assert records["value"].dtype == np.float32 and records["prob"].dtype == np.float32
        assert np.isfinite(records["value"]).all() and np.isfinite(records["prob"]).all()
        # the rows that do not act hold what an episode holds for them
        idle = ~records["active"]
        assert (records["action"][idle] == 0).all() and (records["prob"][idle] == 1).all()
        assert (records["value"][idle] == 0).all() and records["action"].shape == (8, 4, 2)
    # without ``counters`` the program has three outputs, and the same records
    state, hidden = venv.init(4, jax.random.PRNGKey(0)), module.initial_state((4, 2))
    again = plain(params, state, hidden, jax.random.fold_in(jax.random.PRNGKey(5), 0))
    assert len(again) == 3
    for name, value in jax.device_get(again[2]).items():
        np.testing.assert_array_equal(value, outs[0][0][name], err_msg=name)


def test_the_hidden_trees_passes_bear_state_commit_and_the_nets_scopes(rollout):
    """In the compiled rollout: the acting rows' gather and the scatters back
    under ``state_commit`` where the net runs (``rollout_policy``), and no
    pass over the tree outside it: the reset's multiply is the gathered rows';
    the net's scopes inside ``rollout_policy``; a net without hidden has no
    such op."""
    *_, lowered, _ = rollout
    text = lowered.compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    commit = [n for n in names if f"/{device_rollout.COMMIT_SCOPE}/" in n + "/"]
    assert commit and not any(device_rollout.RESET_SCOPE in n for n in commit)
    assert all(device_rollout.POLICY_SCOPE in n for n in commit)
    assert any("scatter" in n for n in commit)
    policy = [n for n in names if f"/{device_rollout.POLICY_SCOPE}/" in n]
    for scope in ("ssd", "route", "experts", "shared_expert", "attn", "norm"):
        assert any(f"/{scope}/" in n + "/" for n in policy), scope
    assert device_rollout.COMMIT_SCOPE not in device_rollout.STREAM_SCOPES
    # nothing but a scatter (and the fusion round it) yields an array of a
    # leaf's whole (lanes, players, ...) shape: no select and no multiply goes
    # over both players' rows
    module = rollout[0]
    shapes = {"f32[%s]" % ",".join(map(str, leaf.shape))
              for leaf in jax.tree.leaves(module.initial_state((4, 2))) if leaf.ndim > 2}
    made = {op for shape, op in re.findall(r"= (f32\[[0-9,]+\])\S* (\w[\w-]*)\(", text)
            if shape in shapes}
    # (XLA:CPU copies a ring between its two scatters; what the TPU's compiler
    # makes of the cell's own program is tests/test_chip_compile.py's)
    assert "scatter" in made and made <= {
        "scatter", "fusion", "get-tuple-element", "parameter", "copy"}, made


# -- the row path against the whole tree's reset and select ----------------------


def _whole_tree_step(module, venv):
    """One step of the streaming rollout as it was before a step was the
    acting rows' alone: the net on all lanes x players rows, the whole hidden
    tree multiplied by ``~reset`` and selected over where observed.  Written
    here from ``module.apply``; -> jitted (params, state, hidden, the step's
    key) -> (state, hidden, record)."""
    def step(params, state, hidden, key_t):
        kr, ka, kf = jax.random.split(key_t, 3)
        reset = state["done"]
        state = venv.reset_done(state, kr)
        hidden = jax.tree.map(lambda h: h * ~reset.reshape((-1,) + (1,) * (h.ndim - 1)), hidden)
        active = state["active"]
        B, P = active.shape
        flat = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: x.reshape((B * P,) + x.shape[2:]), tree)
        out = module.apply({"params": params}, flat(venv.observation(state)), flat(hidden))
        hidden = jax.tree.map(
            lambda h, nh: jnp.where(active.reshape((B, P) + (1,) * (h.ndim - 2)),
                                    nh.reshape(h.shape), h), hidden, out["hidden"])
        logits = out["policy"].astype(jnp.float32).reshape(B, P, -1)
        masked = jnp.where(venv.legal_mask_all(state), logits, logits - device_rollout.ILLEGAL)
        action = jnp.argmax(masked + jax.random.gumbel(ka, masked.shape), axis=-1).astype(jnp.int32)
        prob = jnp.take_along_axis(jax.nn.softmax(masked, axis=-1), action[..., None], axis=-1)[..., 0]
        record = {"active": active, "reset": reset, "action": action, "prob": prob,
                  "value": out["value"].astype(jnp.float32).reshape(B, P)}
        state = venv.step(state, action, kf)
        record.update(done=state["done"], outcome=venv.outcome_scores(state))
        return state, hidden, record

    return jax.jit(step)


@pytest.mark.parametrize("state_size", [16, 128], ids=["lines", "kernel"])
def test_a_step_of_the_acting_rows_is_a_step_of_the_whole_tree(state_size):
    """Float32, 4 lanes, a dispatch a step until a lane's game has ended,
    begun again and both its players have moved: every dispatch's records at
    the acting rows and the hidden tree it leaves equal the whole tree's
    reset, apply and select on the same inputs: the row that does not act
    bit for bit (zeros where the lane's game has just begun: both players
    start from a zero state), the acting row to float32 rounding.  With
    ``state_size`` 128 the SSM leaves go through ``ops/ssd.py``'s kernel (its
    interpreter), with 16 through the lines round a gather."""
    from benchmark import traffic
    from handyrl_tpu.ops import ssd

    _, _, env, module = _geister({"batch_size": 2, "burn_in_steps": 0, "forward_steps": 8},
                                 state_size=state_size, mamba_heads=16, mamba_head_dim=8,
                                 memory_len=8)
    params, venv = traffic.seeded_params(module, env, 3), env.vector_env()
    fn = build_streaming_fn(venv, module, 4, 1, use_observe_mask=False, counters=True)
    whole = _whole_tree_step(module, venv)
    state, hidden = venv.init(4, jax.random.PRNGKey(2)), module.initial_state((4, 2))
    begun_lanes, moved_since = set(), {}
    for i in range(260):
        key = jax.random.fold_in(jax.random.PRNGKey(11), i)
        want_state, want_hidden, want = jax.device_get(
            whole(params, state, hidden, jax.random.split(key, 1)[0]))
        state, hidden, records, counted = fn(params, state, hidden, key)    # donates both
        got_hidden, got = jax.device_get((hidden, records))
        assert counted[device_rollout.ROWS_APPLIED] == 4
        acting, reset = want["active"], want["reset"]
        assert (acting.sum(axis=1) == 1).all()
        np.testing.assert_array_equal(got["active"][0], acting)
        np.testing.assert_array_equal(got["action"][0][acting], want["action"][acting])
        np.testing.assert_allclose(got["prob"][0][acting], want["prob"][acting], rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(got["value"][0][acting], want["value"][acting], rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(got["done"][0], want["done"])
        np.testing.assert_array_equal(got["outcome"][0], want["outcome"])
        for (path, leaf), ref in zip(jax.tree_util.tree_leaves_with_path(got_hidden),
                                     jax.tree.leaves(want_hidden)):
            np.testing.assert_array_equal(leaf[~acting], ref[~acting], err_msg=str(path))
            np.testing.assert_allclose(leaf[acting], ref[acting], rtol=1e-4, atol=1e-5,
                                       err_msg=str(path))
            assert not leaf[~acting][reset].any(), path      # begun: the other player's row is zeros
        for name, leaf in want_state.items():
            np.testing.assert_array_equal(np.asarray(state[name]), leaf, err_msg=name)
        for lane in np.flatnonzero(reset):
            begun_lanes.add(int(lane))
            moved_since[int(lane)] = set()
        for lane in moved_since:
            moved_since[lane].add(int(np.argmax(acting[lane])))
        if any(len(moved) == 2 for moved in moved_since.values()) and i > 8:
            break
    assert begun_lanes and any(len(moved) == 2 for moved in moved_since.values()), (
        "no game ended and began again inside 260 steps")
    assert ssd.ROW_PATHS[("float32", 2, 16, 8, state_size, 1)]["path"] == (
        "kernel" if state_size == 128 else "gather")


def test_a_dispatch_of_many_steps_is_as_many_steps_of_the_whole_tree():
    """The scan's carry: one dispatch of 12 steps (the SSM leaves through the
    kernel's interpreter, stepped where the carry lies) leaves the records and
    the hidden tree that 12 steps of the whole tree's reset, apply and select
    leave on the same keys."""
    from benchmark import traffic

    _, _, env, module = _geister({"batch_size": 2, "burn_in_steps": 0, "forward_steps": 8},
                                 state_size=128, mamba_heads=16, mamba_head_dim=8, memory_len=8)
    params, venv = traffic.seeded_params(module, env, 3), env.vector_env()
    many = build_streaming_fn(venv, module, 3, 12, use_observe_mask=False)
    whole = _whole_tree_step(module, venv)
    key = jax.random.PRNGKey(4)
    state, hidden = venv.init(3, jax.random.PRNGKey(2)), module.initial_state((3, 2))
    _, got_hidden, records = many(params, venv.init(3, jax.random.PRNGKey(2)),
                                  module.initial_state((3, 2)), key)
    for t, key_t in enumerate(jax.random.split(key, 12)):
        state, hidden, want = whole(params, state, hidden, key_t)
        acting = np.asarray(want["active"])
        np.testing.assert_array_equal(records["action"][t][acting], want["action"][acting])
        np.testing.assert_allclose(records["prob"][t][acting], want["prob"][acting],
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_array_equal(records["done"][t], want["done"])
    for a, b in zip(jax.tree.leaves(got_hidden), jax.tree.leaves(hidden)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_a_net_without_hidden_keeps_its_program_and_its_options():
    from handyrl_tpu.utils.compile_cache import scoped_program_options

    env = make_env({"env": "HungryGeese"})
    module, venv = env.net(), env.vector_env()
    fn = build_streaming_fn(venv, module, 2, 2)
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    params = module.init(jax.random.PRNGKey(0), obs, None)["params"]
    text = fn.lower(params, venv.init(2, jax.random.PRNGKey(0)), None,
                    jax.random.PRNGKey(1)).compile().as_text()
    assert device_rollout.COMMIT_SCOPE not in text
    # the cache key it had: the five scopes' names and no sixth
    assert scoped_program_options(*device_rollout.STREAM_SCOPES) != scoped_program_options(
        *device_rollout.STREAM_SCOPES, device_rollout.COMMIT_SCOPE)


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_the_actor_loop_ships_whole_dispatches_and_installs_polled_weights_in_param_dtype(tmp_path):
    """``actor_loop`` on this process's device against a loopback gateway:
    every record batch is lanes x k steps; float32 parameters published by
    the learner's side are installed once, in bfloat16, on the device, and
    act from the next dispatch on; the spans and events are written."""
    from handyrl_tpu.runtime.plane import PlaneGateway

    port = _free_port()
    dist = {"role": "actor", "coordinator_address": f"127.0.0.1:{port}", "plane_port": port,
            "initialization_timeout": 30.0}
    config = _config(param_dtype="bfloat16", experts_held=4)
    cfg = normalize_args({"env_args": dict(config["env_args"]), "train_args": {
        "observation": False, "device_rollout_games": 4, "device_replay_k_steps": 8,
        "seed": 7, "distributed": dist}})
    env = make_env(cfg["env_args"])
    module = env.net()
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    fresh = jax.device_get(HybridNet(
        num_actions=module.num_actions, with_return=True, **dict(NET, experts_held=4)).init(
            jax.random.PRNGKey(99), obs, None)["params"])
    assert {x.dtype.name for x in jax.tree.leaves(fresh)} == {"float32"}

    batches, stop = [], threading.Event()

    def on_records(records):
        batches.append(records)
        if len(batches) == 2:
            gateway.publish(fresh, 1)       # the learner publishes: the next reply hints at it
        if len(batches) == 5:
            gateway.begin_stop()

    gateway = PlaneGateway(dist, on_records=on_records)
    gateway.start()
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    # the case's own time limit: a gateway that never says stop ends the loop by
    # the event it watches (then ``dispatches`` below is not 5), not the worker's run
    limit = threading.Timer(240.0, stop.set)
    limit.daemon = True
    limit.start()
    try:
        done = actor_host.actor_loop(cfg, jax.devices()[:1], stop)
    finally:
        limit.cancel()
        gateway.stop()
        trace.shutdown()
    assert done["dispatches"] == len(batches) == 5 and gateway.actor_host_losses == 0
    for records in batches:
        assert records["action"].shape == (8, 4, 2) and records["value"].dtype == np.float32
        assert np.isfinite(records["prob"]).all()
    # installed as the module holds them: bfloat16, on the device, the published values
    held = done["params"]
    assert {x.dtype.name for x in jax.tree.leaves(held)} == {"bfloat16"}
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(held))
    np.testing.assert_array_equal(
        np.asarray(held["layer1"]["mixer"]["w1"]),
        np.asarray(fresh["layer1"]["mixer"]["w1"]).astype(jnp.bfloat16))
    records = trace.read_trace(str(tmp_path / "trace.jsonl"))
    names = [r["name"] for r in records]
    # the sixth batch was answered with the gateway's stop: made and fetched, never ingested
    for span in ("actor.dispatch", "actor.fetch", "actor.ship"):
        assert names.count(span) == 6, span
    assert names.count("actor.poll") == 1 and names.count("actor.counters") == 6
    weights = [r["attrs"] for r in records if r["name"] == "actor.weights"]
    assert len(weights) == 2        # made from the seed, then installed
    size = sum(x.size for x in jax.tree.leaves(held))
    assert all(w == {"parameters": size, "bytes": 2 * size, "dtype": "bfloat16"} for w in weights)
    counted = [r["attrs"] for r in records if r["name"] == "actor.counters"]
    assert all(c["buffer_slots"] > c["rows_held"] > 0 for c in counted)
    # ``observation: false`` on Geister: a step is the 4 acting rows' of 8
    assert all(c["rows_applied"] == 8 * 4 for c in counted)
    # and, once, how the acting rows of the SSM states are stepped: the tiny
    # state (16 wide) keeps the lines round a gather
    paths = [r["attrs"] for r in records if r["name"] == "model.ssd_rows_path"]
    assert [p["path"] for p in paths if p["state_size"] == 16 and p["heads"] == 4
            and p["head_dim"] == 16] == ["gather"]
    assert len(paths) == len({tuple(sorted(p.items())) for p in paths})
