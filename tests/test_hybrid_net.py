"""HybridNet (models/hybrid.py: Mamba-2 mixers, routed experts with a shared
one, grouped-query attention, by a layer-pattern string) at tiny widths on
the CPU, against the plain reference of the configuration it was written
for (benchmark/reference/nemotron_twotower_30b_a3b.py, which imports
nothing from handyrl_tpu.models), through ``forward_prediction`` and the
train step.
"""

import importlib.util
import json
import math
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import HybridNet
from handyrl_tpu.models.hybrid import ExpertLayer
from handyrl_tpu.ops import routed_experts
from handyrl_tpu.ops.grouped_product import BLOCK, FEW_ROWS, grouped_dot
from handyrl_tpu.ops.routed_experts import (
    EXPERTS_SCOPE, SHARES, _owners, block_rows, choose, held_mix, row_buffer)
from handyrl_tpu.ops.ssd import ssd_chunked, ssd_step
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import (
    PACK_MULTIPLE, PACKED_ORDER, forward_prediction, pack_order, sub_jaxprs, trim_burn_in)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    path = os.path.join(REPO, "benchmark", *parts)
    spec = importlib.util.spec_from_file_location("hybrid_" + parts[-1][:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REFERENCE = _load("reference", "nemotron_twotower_30b_a3b.py")
FLOPS = _load("flops", "nemotron_h.py")

NET = dict(
    pattern="MEM*E", d_model=32, norm_eps=1e-5,
    mamba_heads=4, mamba_head_dim=16, n_groups=2, state_size=16, conv_kernel=4, chunk=4,
    n_experts=8, top_k=2, expert_width=32, shared_width=64, routed_scale=2.5,
    experts_held=4, expert_offset=2,
    n_heads=4, n_kv_heads=2, head_dim=16, memory_len=200,
)


def _config(**net):
    return {"name": "tiny_hybrid", "env_args": {"env": "Geister", "net": "hybrid",
                                                "net_args": dict(NET, **net)}}


def _window(seed, rows=3, steps=10, width=7, observed=0.6):
    """(obs, key_mask): ``rows`` sequences of ``steps`` steps, each step
    observed with probability ``observed``."""
    rng = np.random.RandomState(seed)
    obs = {"a": jnp.asarray(rng.randn(rows, steps, width), jnp.float32)}
    return obs, jnp.asarray(rng.rand(rows, steps) < observed, jnp.float32)


def _params(module, obs, seed=0, score_bias=0.0):
    params = module.init(jax.random.PRNGKey(seed), jax.tree.map(lambda x: x[:, 0], obs), None)["params"]
    if score_bias:
        rng = np.random.RandomState(seed)
        for name in [n for n in params if n.startswith("layer")]:
            if "score_bias" in params[name]["mixer"]:
                bias = score_bias * rng.randn(*params[name]["mixer"]["score_bias"].shape)
                params[name]["mixer"]["score_bias"] = jnp.asarray(bias, jnp.float32)
    return params


# -- the system against the plain reference, float32 ----------------------


@pytest.mark.parametrize("pattern", ["M", "E", "*", "MEM*E", "MEMEM*EME"])
def test_window_matches_the_reference_per_mixer_and_whole(pattern):
    config = _config(pattern=pattern)
    module = HybridNet(num_actions=5, with_return=True, **config["env_args"]["net_args"])
    obs, mask = _window(1)
    params = _params(module, obs, score_bias=0.2)
    got = module.apply({"params": params}, obs, None, seq=True, key_mask=mask)
    want = REFERENCE.forward(params, obs, mask, config)
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
    config = _config(pattern="MM")
    module = HybridNet(num_actions=5, **config["env_args"]["net_args"])
    obs, _ = _window(2, steps=steps)
    mask = jnp.ones((3, steps))
    params = _params(module, obs)
    got = module.apply({"params": params}, obs, None, seq=True, key_mask=mask)
    want = REFERENCE.forward(params, obs, mask, config)
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


def _geister(train_args, seed=1, **net):
    config = _config(**net)
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
        {"batch_size": 3, "burn_in_steps": 3, "forward_steps": 9})
    assert isinstance(module, HybridNet) and module.with_return
    params = traffic.seeded_params(module, env, 1)
    batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
    # Geister's players observe on their own turns: unobserved steps abound
    assert 0.2 < float(np.mean(batch["observation_mask"])) < 0.8
    return config, args, module, params, batch


def test_whole_window_matches_the_scan_path_with_unobserved_steps_and_burn_in(geister):
    _, args, module, params, batch = geister
    window = jax.jit(lambda p, b: forward_prediction(module, p, b, args))(params, batch)
    scan = jax.jit(lambda p, b: forward_prediction(
        module, p, b, dict(args, seq_forward=False)))(params, batch)
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(window[head], scan[head], atol=2e-5)
    assert "choices" not in scan and set(window["choices"]) == {"forward", "window_start"}
    chosen = window["choices"]["forward"]
    assert set(chosen) == {"layer1", "layer4"} and chosen["layer1"].shape == (3, 9, 2, 2)
    assert chosen["layer1"].dtype == jnp.int32


def test_a_remat_rung_the_net_lacks_is_refused_by_name():
    obs, mask = _window(0)
    module = HybridNet(num_actions=3, **NET)
    params = _params(module, obs)
    with pytest.raises(ValueError, match=r"HybridNet: remat='attn' not one of"):
        module.apply({"params": params}, obs, None, seq=True, key_mask=mask, remat="attn")


def test_burn_in_stops_gradients_through_every_carried_state(geister):
    """The scan path's burn-in rule: what the burn-in steps leave carries no
    gradient, so the window path's parameter gradient equals the scan's."""
    _, args, module, params, batch = geister

    def loss(p, seq_forward):
        out = forward_prediction(module, p, batch, dict(args, seq_forward=seq_forward))
        return sum(jnp.sum(jnp.square(jnp.where(jnp.abs(out[k]) < 1e6, out[k], 0.0)))
                   for k in ("policy", "value", "return"))

    window = jax.grad(loss)(params, True)
    scan = jax.grad(loss)(params, False)
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

    sound = lambda p, b: forward_prediction(module, p, b, args)  # noqa: E731
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

    got, want = jax.grad(system)(params), jax.grad(reference)(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            a, b, atol=2e-4 * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))


# -- the window packed to a bound the host read from the batch -------------


def _seen(batch):
    return np.moveaxis(np.asarray(batch["observation_mask"])[..., 0] > 0, 1, 2)   # (B, P, T)


def _with_order(batch, burn_in, bounds):
    """The batch with a hand-made ``packed_order`` at ``bounds`` (a part
    whose bound is None gets its row maximum exactly)."""
    seen, order = _seen(batch), {}
    for part, steps in (("burn_in", seen[..., :burn_in]), ("forward", seen[..., burn_in:])):
        if steps.shape[-1]:
            bound = bounds.get(part)
            order[part] = pack_order(steps, int(steps.sum(-1).max()) if bound is None else bound)
    return dict(batch, **{PACKED_ORDER: order})


@pytest.fixture(scope="module")
def long_windows():
    """Geister windows of 40 forward steps after 4 and after 0 burn-in steps,
    one row of each cut short as a game that ends inside its window is, and
    one batch in which every step of every player carries an observation."""
    from benchmark import traffic

    made = {}
    for name, burn_in in (("burn_in_4", 4), ("burn_in_0", 0)):
        _, args, env, module = _geister(
            {"batch_size": 3, "burn_in_steps": burn_in, "forward_steps": 40})
        batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
        for key in ("observation_mask", "turn_mask", "episode_mask"):
            batch[key][1, burn_in + 27:] = 0      # end-of-game padding
        batch["action_mask"][1, burn_in + 27:] = 1e32
        made[name] = (args, batch)
    args, batch = made["burn_in_4"]
    maxima = _seen(batch)[..., 4:].sum(-1)
    assert maxima.max() == 20 and maxima.min() < 16        # unobserved steps, rows of two lengths
    made["all_observed"] = (args, dict(
        batch, observation_mask=np.ones_like(batch["observation_mask"]),
        turn_mask=np.ones_like(batch["turn_mask"])))
    return module, traffic.seeded_params(module, env, 1), made


def _forward_and_gradient(module, args):
    """(params, batch) -> (forward_prediction's outputs, every parameter's
    gradient of a sum over the heads), one jitted call."""
    def loss(p, b):
        out = forward_prediction(module, p, b, args)
        return sum(jnp.sum(jnp.square(jnp.where(jnp.abs(out[k]) < 1e6, out[k], 0.0)))
                   for k in ("policy", "value", "return")), out

    return jax.jit(lambda p, b: jax.grad(loss, has_aux=True)(p, b)[::-1])


@pytest.mark.parametrize("windows,bounds,slots", [
    # the bound is the row maximum exactly, in both parts
    ("burn_in_4", {"burn_in": None, "forward": None}, 6 * (2 + 20)),
    # the bucket above it, as put_batch makes it; the burn-in part is shorter than a bucket
    ("burn_in_4", "put_batch", 6 * (4 + 32)),
    # a hand-made order as long as its part: the host's order where the device's argsort was
    ("burn_in_4", {"burn_in": 4, "forward": 40}, 6 * (4 + 40)),
    ("burn_in_0", "put_batch", 6 * 32),
    ("all_observed", {"burn_in": 4, "forward": 40}, 6 * (4 + 40)),
])
def test_a_packed_window_equals_the_whole_one(long_windows, windows, bounds, slots):
    """Heads, choices, counters and every parameter's gradient with a
    ``packed_order`` equal those without one, in float32 under ``highest``."""
    module, params, made = long_windows
    args, batch = made[windows]
    if bounds == "put_batch":
        packed = jax.device_get(TrainContext(module, args, make_mesh({"dp": 1})).put_batch(batch))
    else:
        packed = _with_order(batch, args["burn_in_steps"], bounds)
    assert sum(order.shape[0] * order.shape[1] * order.shape[2]
               for order in packed[PACKED_ORDER].values()) == slots
    both = _forward_and_gradient(module, args)
    with jax.default_matmul_precision("highest"):
        (whole, whole_grad), (got, got_grad) = both(params, batch), both(params, packed)
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(got[head], whole[head], atol=1e-5)
    assert all(jax.tree.leaves(jax.tree.map(np.array_equal, got["choices"], whole["choices"])))
    counted = dict(got["counters"])
    assert counted.pop("packed_slots") == slots and counted["packed_dropped"] == 0
    assert whole["counters"]["packed_slots"] == 6 * (args["burn_in_steps"] + 40)
    assert counted["observed_steps"] == float(np.sum(batch["observation_mask"]))
    # the row buffers are sized from the slots the mixers run over
    assert counted.pop("buffer_slots") <= whole["counters"]["buffer_slots"]
    # float32: the plain products run every slot of a buffer
    assert counted.pop("slots_run") <= whole["counters"]["slots_run"]
    assert counted == {k: v for k, v in whole["counters"].items()
                       if k not in ("packed_slots", "buffer_slots", "slots_run")}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grad),
                            jax.tree.leaves(whole_grad)):
        np.testing.assert_allclose(
            a, b, atol=1e-5 * max(1.0, float(jnp.abs(b).max())), err_msg=str(path))


def test_a_packed_window_matches_the_scan_path(long_windows):
    module, params, made = long_windows
    args, batch = made["burn_in_4"]
    packed = _with_order(batch, 4, {"burn_in": None, "forward": 32})
    window = jax.jit(lambda p, b: forward_prediction(module, p, b, args))(params, packed)
    scan = jax.jit(lambda p, b: forward_prediction(
        module, p, b, dict(args, seq_forward=False)))(params, batch)
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(window[head], scan[head], atol=2e-5)


def test_a_packed_window_hands_on_the_states_the_whole_one_does(long_windows):
    """What each mixer carries out of the burn-in steps and out of the
    window: the SSM state and the conv's tail whole, the attention layer's
    count, and its keys and values on the steps that hold a token."""
    module, params, made = long_windows
    args, batch = made["burn_in_4"]
    packed = _with_order(batch, 4, {"burn_in": None, "forward": 32})
    rows = lambda x: np.moveaxis(np.asarray(x), 2, 1).reshape((6, 44) + x.shape[3:])  # noqa: E731
    obs, mask = jax.tree.map(rows, batch["observation"]), rows(batch["observation_mask"])[..., 0]

    def states(order):
        _, kept = module.apply(
            {"params": params}, obs, None, seq=True, key_mask=mask, burn_in=4, packed_order=order,
            capture_intermediates=lambda layer, _: (layer.name or "").startswith("layer"),
            mutable=["intermediates"])
        return {name: [call[1] for call in layer["__call__"]]     # (x, state, routed) a call
                for name, layer in kept["intermediates"].items()}

    order = jax.tree.map(lambda x: x.reshape((6,) + x.shape[2:]), packed[PACKED_ORDER])
    with jax.default_matmul_precision("highest"):
        whole, got = states(None), states(order)
    assert set(got) == {f"layer{i}" for i in range(5)}
    for name, kind in zip(sorted(got), NET["pattern"]):
        for part, (a, b) in enumerate(zip(got[name], whole[name])):
            if kind == "*":
                assert np.array_equal(a["n"], b["n"]) and a["n"].max() > 0
                # slots: the burn-in steps' (2 packed, 4 whole), then the forward steps'
                before, first = np.asarray(got[name][0]["n"]), (2, 4)
                for key in ("k", "v"):
                    assert a[key].shape[1] == (2, 2 + 32)[part] and b[key].shape[1] == (4, 44)[part]
                    for lo, count in ((0, before), (None, np.asarray(a["n"]) - before))[:part + 1]:
                        ours, theirs = (np.asarray(x[key])[:, at if lo is None else lo:]
                                        for x, at in ((a, first[0]), (b, first[1])))
                        held = np.arange(ours.shape[1])[None, :] < count[:, None]
                        np.testing.assert_allclose(
                            ours[held], theirs[:, :ours.shape[1]][held], atol=1e-5)
            else:
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                    np.testing.assert_allclose(x, y, atol=1e-5)


def test_put_batch_reads_the_bound_and_never_lowers_it(long_windows, tmp_path, monkeypatch):
    """A multiple of 32 capped at the part; the largest handed out stays; a
    stack gets one bound; a part at its full length gets no leaf; several
    processes are left alone; one event a new bound."""
    from handyrl_tpu.utils import trace

    module, _, made = long_windows
    args, batch = made["burn_in_4"]
    short = jax.tree.map(np.copy, batch)
    short["observation_mask"][:, 4 + 20:] = 0      # at most 10 observed forward steps a row
    args = dict(args, forward_steps=72)
    longer = lambda b: jax.tree.map(  # noqa: E731
        lambda x: np.concatenate([x, x[:, 12:]], axis=1) if x.shape[1] == 44 else x, b)
    wide, tall = longer(batch), longer(short)   # 72 forward steps: 36 observed on some row, and 16
    assert PACK_MULTIPLE == 32 and _seen(wide)[..., 4:].sum(-1).max() == 36
    assert _seen(tall)[..., 4:].sum(-1).max() == 16

    def shapes(device_batch):
        return {k: v.shape for k, v in device_batch.get(PACKED_ORDER, {}).items()}

    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        ctx = TrainContext(module, args, make_mesh({"dp": 1}))
        assert shapes(ctx.put_batch(tall)) == {"burn_in": (3, 2, 4), "forward": (3, 2, 32)}
        assert shapes(ctx.put_batch(wide)) == {"burn_in": (3, 2, 4), "forward": (3, 2, 64)}
        # the high-water mark: a batch that would fit 32 gets 64, alone or stacked
        assert shapes(ctx.put_batch(tall))["forward"] == (3, 2, 64)
        assert shapes(ctx.put_batches([tall, tall]))["forward"] == (2, 3, 2, 64)
        fresh = TrainContext(module, args, make_mesh({"dp": 1}))
        assert shapes(fresh.put_batches([tall, wide, tall]))["forward"] == (3, 3, 2, 64)
    finally:
        trace.shutdown()
    events = [r["attrs"] for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
              if r["name"] == "train.packed_bound"]
    assert events == [
        {"plane": "learner", "burn_in": 4, "forward": 32, "burn_in_steps": 4, "forward_steps": 72},
        {"plane": "learner", "burn_in": 4, "forward": 64, "burn_in_steps": 4, "forward_steps": 72},
        {"plane": "learner", "burn_in": 4, "forward": 64, "burn_in_steps": 4, "forward_steps": 72},
    ]

    # every part at its length: the batch as it came, and the program it always ran
    args, batch = made["burn_in_4"]
    ctx = TrainContext(module, dict(args, forward_steps=9), make_mesh({"dp": 1}))
    cut = jax.tree.map(lambda x: x[:, :13] if x.shape[1] == 44 else x, batch)
    assert PACKED_ORDER not in ctx.put_batch(cut) and ctx._packed_bounds == {"burn_in": 4, "forward": 9}
    ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    assert PACKED_ORDER not in ctx.put_batch(made["all_observed"][1])
    assert PACKED_ORDER not in ctx.put_batch(batch)        # 40 stays: no program of 32 after it
    # every process would have to agree on the shape: left alone, as _compact_ff
    ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert ctx._pack([batch]) == [batch] and ctx._packed_bounds == {}


def test_an_order_too_short_is_counted_and_the_loss_sees_the_batch_it_saw(long_windows):
    module, params, made = long_windows
    args, batch = made["burn_in_4"]
    ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    packed = ctx._pack([batch])[0]
    trimmed = trim_burn_in(packed, 4)
    assert PACKED_ORDER not in trimmed and set(trimmed) == set(batch)
    for a, b in zip(jax.tree.leaves(trimmed), jax.tree.leaves(trim_burn_in(batch, 4))):
        assert np.array_equal(a, b)

    metrics = {}
    for name, fed in (("whole", batch), ("packed", packed),
                      ("short", _with_order(batch, 4, {"burn_in": 4, "forward": 16}))):
        state = ctx.init_state(params)
        device_batch = ctx._put_sharded(fed, ctx._batch_shard, 3)
        metrics[name] = jax.device_get(ctx.train_step(state, device_batch, 1e-4)[1])
    whole, got, short = (metrics[k] for k in ("whole", "packed", "short"))
    for key in ("p", "v", "r", "ent", "total", "dcnt", "counter_rows_held", "counter_observed_steps"):
        assert got[key] == pytest.approx(whole[key], rel=1e-5), key
    assert got["counter_packed_dropped"] == whole["counter_packed_dropped"] == 0
    assert (whole["counter_packed_slots"], got["counter_packed_slots"]) == (6 * 44, 6 * 36)
    # rows with 20 observed steps and 16 slots: seen, not silently lost
    lost = float(np.maximum(_seen(batch)[..., 4:].sum(-1) - 16, 0).sum())
    assert short["counter_packed_dropped"] == lost > 0
    assert short["counter_observed_steps"] == whole["counter_observed_steps"]


# -- the expert layer's share -----------------------------------------------


def _expert_layer(held, offset, experts=32, top_k=6):
    return ExpertLayer(d_model=16, n_experts=experts, top_k=top_k, expert_width=8,
                       shared_width=24, routed_scale=2.5, experts_held=held, expert_offset=offset)


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """Each of sixteen chips holds 2 of 32 experts, routes over all 32 and
    adds its own experts' terms: the shares' routed parts, with the shared
    expert counted once, are the uncut reference's whole layer."""
    h = jnp.asarray(np.random.RandomState(3).randn(5, 7, 16), jnp.float32)
    whole = _expert_layer(32, 0)
    params = whole.init(jax.random.PRNGKey(0), h)["params"]
    net = dict(top_k=6, routed_scale=2.5, experts_held=32, expert_offset=0)
    want, chosen = REFERENCE.experts(params, h, net)
    shared = jnp.square(jax.nn.relu(h @ params["shared_up"]["kernel"])) @ params["shared_down"]["kernel"]
    total = shared
    for share in range(16):
        held = dict(params, w1=params["w1"][2 * share:2 * share + 2],
                    w2=params["w2"][2 * share:2 * share + 2])
        out, picked, counts, _ = _expert_layer(2, 2 * share).apply({"params": held}, h)
        assert np.array_equal(np.sort(picked, -1), np.sort(chosen, -1))     # routes over all
        assert int(counts["rows"].sum()) == int(((chosen >= 2 * share) & (chosen < 2 * share + 2)).sum())
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_score_bias_changes_choices_and_not_gates():
    scores = jax.nn.sigmoid(jnp.asarray(np.random.RandomState(4).randn(50, 16), jnp.float32))
    bias = jnp.zeros(16).at[3].set(5.0)
    plain, _ = choose(scores, jnp.zeros(16), 4, 2.5)
    chosen, gates = choose(scores, bias, 4, 2.5)
    assert bool((chosen == 3).any(axis=-1).all()) and not np.array_equal(plain, chosen)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)      # the bias is not in the gates
    np.testing.assert_allclose(gates, 2.5 * picked / picked.sum(axis=-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(axis=-1), 2.5, rtol=1e-6)


@pytest.mark.parametrize("tokens", [40, 600, 3000])
def test_one_expert_given_every_token_drops_none(tokens):
    """Every token chooses the same two held experts: the rows outgrow the
    buffer (a uniform router's share and a block of padding an expert) and
    further passes take them."""
    rng = np.random.RandomState(5)
    d, width, held, experts, k = 16, 8, 4, 32, 2
    h = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    w1 = jnp.asarray(rng.randn(held, d, width) / 4, jnp.float32)
    w2 = jnp.asarray(rng.randn(held, width, d) / 3, jnp.float32)
    chosen = jnp.tile(jnp.asarray([[9, 10]], jnp.int32), (tokens, 1))
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.asarray(rng.rand(tokens) > 0.1)
    blocks, passes = row_buffer(tokens, k, held, experts, BLOCK)
    assert (passes > 1 and blocks * BLOCK < int(valid.sum()) * k) == (tokens > 500)
    out, counts = jax.jit(lambda *a: held_mix(*a, 8, experts))(h, chosen, gates, valid, w1, w2)
    assert counts["rows"].tolist() == [0, int(valid.sum()), int(valid.sum()), 0]
    act = lambda e: jnp.square(jax.nn.relu(h @ w1[e])) @ w2[e]  # noqa: E731
    want = valid[:, None] * (gates[:, :1] * act(1) + gates[:, 1:] * act(2))
    np.testing.assert_allclose(out, want, atol=2e-5)
    # and its gradient
    grad = jax.grad(lambda w: jnp.sum(held_mix(h, chosen, gates, valid, w, w2, 8, experts)[0] ** 2))(w1)
    want = jax.grad(lambda w: jnp.sum((valid[:, None] * (
        gates[:, :1] * (jnp.square(jax.nn.relu(h @ w[1])) @ w2[1])
        + gates[:, 1:] * (jnp.square(jax.nn.relu(h @ w[2])) @ w2[2]))) ** 2))(w1)
    np.testing.assert_allclose(grad, want, atol=2e-4 * float(jnp.abs(want).max()))


def _by_expert(h, chosen, gates, valid, w1, w2, offset):
    """The plain loop: for every held expert, every token that chose it,
    with the kernel's roundings (float32 accumulation, relu^2 in float32,
    the operands' dtype between the products and into the sum)."""
    out = jnp.zeros(h.shape, jnp.float32)
    for e in range(w1.shape[0]):
        up = jnp.dot(h, w1[e], preferred_element_type=jnp.float32)
        act = jnp.square(jax.nn.relu(up)).astype(h.dtype)
        down = jnp.dot(act, w2[e], preferred_element_type=jnp.float32)
        gate = jnp.where((chosen == e + offset) & valid[:, None], gates, 0.0).sum(axis=1)
        out = out + (down * gate[:, None]).astype(h.dtype)
    return out.astype(h.dtype)


def _close(got, want, dtype, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    # bfloat16 keeps 8 bits: sums of a few hundred rounded terms in two orders
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, atol=tol * max(1.0, float(np.abs(want).max())),
                               err_msg=what)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("k,n", [(64, 192), (192, 64), (64, 1856)])
def test_grouped_dot_is_each_blocks_rows_by_its_experts_weights(dtype, k, n):
    """The kernel (in the interpreter) against a loop over the blocks, forward
    and both gradients, at widths under a tile, not a multiple of 128, and
    over a tile with a partial last one; an expert with no block gets a zero
    gradient."""
    rng = np.random.RandomState(11)
    owner = jnp.asarray([0, 2, 2, 2, 4, 4], jnp.int32)       # 1 and 3 hold no block
    x = jnp.asarray(rng.randn(owner.size * BLOCK, k), dtype)
    w = jnp.asarray(rng.randn(5, k, n) / np.sqrt(k), dtype)

    def loop(x, w):
        blocks = x.reshape(owner.size, BLOCK, k)
        return jnp.concatenate([
            jnp.dot(blocks[b], w[int(e)], preferred_element_type=jnp.float32)
            for b, e in enumerate(owner)])

    _close(grouped_dot(x, w, owner, True), loop(x, w), dtype, "forward")
    weigh = jnp.asarray(rng.randn(x.shape[0], n), jnp.float32)
    grads = lambda fn: jax.grad(lambda x, w: jnp.sum(fn(x, w) * weigh), argnums=(0, 1))(x, w)  # noqa: E731
    (dx, dw), (want_dx, want_dw) = grads(lambda x, w: grouped_dot(x, w, owner, True)), grads(loop)
    assert dx.dtype == dtype and dw.dtype == dtype
    _close(dx, want_dx, dtype, "rows' cotangent")
    _close(dw, want_dw, dtype, "weights' gradient")
    assert not np.asarray(dw[1], np.float32).any() and not np.asarray(dw[3], np.float32).any()


@pytest.mark.parametrize("k,n,tiles", [(64, 192, 1), (2048, 4096, 2)],
                         ids=["whole_tile", "column_tiles"])
def test_weight_sums_add_to_the_sum_a_loop_carries(k, n, tiles):
    """``_weight_sums`` with ``into`` (the interpreter), at a shape that is
    one tile and at ``zaya1_8b``'s fused (2048, 4096), which goes in two
    column tiles: past the first pass a group with blocks gets ``into`` plus
    its plain sum, added in float32 and rounded once, a group with none
    keeps ``into``; on the first pass the result is the plain sum whatever
    ``into`` holds (NaNs here), bit for bit what the kernel gives without
    ``into``."""
    from handyrl_tpu.ops import grouped_product
    from handyrl_tpu.ops.grouped_product import _weight_sums

    # 10 bytes an element with the carried sum's tile: ``_weight_sums``' rule
    assert (10 * k * n > grouped_product._SUMS_BYTES) == (tiles > 1)
    key = jax.random.PRNGKey(k)
    x = jax.random.normal(key, (3 * 16, k), jnp.bfloat16)
    dy = jax.random.normal(jax.random.fold_in(key, 1), (3 * 16, n), jnp.bfloat16)
    owner = jnp.array([0, 2, 2], jnp.int32)        # 1 and 3 hold no block
    into = 8 * jax.random.normal(jax.random.fold_in(key, 2), (4, k, n), jnp.bfloat16)
    plain = _weight_sums(x, dy, owner, 4, jnp.bfloat16, True)
    exact = _weight_sums(x, dy, owner, 4, jnp.float32, True)
    assert np.asarray(exact[0]).any() and np.asarray(exact[2]).any()
    assert not np.asarray(exact[1]).any() and not np.asarray(exact[3]).any()

    later = _weight_sums(x, dy, owner, 4, jnp.bfloat16, True, into, jnp.bool_(False))
    want = (into.astype(jnp.float32) + exact).astype(jnp.bfloat16)
    assert later.dtype == jnp.bfloat16 and bool((later == want).all())
    assert bool((later[1] == into[1]).all()) and bool((later[3] == into[3]).all())
    assert not bool((later[0] == plain[0]).all())

    first = _weight_sums(x, dy, owner, 4, jnp.bfloat16, True,
                         jnp.full_like(into, jnp.nan), jnp.bool_(True))
    assert bool((first == plain).all())


def _routing(rng, tokens, rows_of, held, offset, k):
    """chosen (tokens, k): expert ``offset + e`` is chosen by exactly
    ``rows_of[e]`` tokens, no token choosing an expert twice; every other
    choice falls on an expert that is not held."""
    picks = np.concatenate([np.full(r, offset + e) for e, r in enumerate(rows_of)])
    assert picks.size <= tokens * k
    picks = np.concatenate([picks, np.full(tokens * k - picks.size, -1)]).reshape(k, tokens).T
    picks = np.where(picks < 0, offset + held + np.arange(k)[None, :], picks)
    assert (np.diff(np.sort(picks, axis=1), axis=1) > 0).all()
    return jnp.asarray(picks[rng.permutation(tokens)], jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("rows_of,passes,experts", [
    ((0, 128, 40, 300), 1, 32),         # an expert with no row, one with exactly a block
    ((500, 0, 257, 129), 2, 32),        # the rows outgrow the buffer once
    ((513, 1, 385, 381), 3, 64),        # and twice: 13 blocks of rows in a buffer of 6
], ids=["one_pass", "two_passes", "three_passes"])
def test_held_mix_is_the_loop_over_experts_and_so_are_its_gradients(monkeypatch, dtype, rows_of,
                                                                    passes, experts):
    """bfloat16 operands go through the grouped kernel (the interpreter
    here), float32 ones through the plain block products: both are the loop
    over experts, forward and for the gradients of ``h``, ``gates``, ``w1``
    and ``w2``, at an expert width that is no multiple of 128.  The kernel
    sums the weights' gradients into the backward loop's carry: with one
    pass they are bit for bit what one pass outside any loop gives, its
    kernels called once without a carried sum."""
    from handyrl_tpu.ops import routed_experts

    rng = np.random.RandomState(7)
    tokens, d, width, held, k, offset = 640, 32, 192, 4, 2, 8
    assert block_rows(tokens, k, experts, dtype) == BLOCK   # 20 or 40 rows an expert: the MXU's tile
    blocks, _ = row_buffer(tokens, k, held, experts, BLOCK)
    assert blocks == math.ceil(SHARES * tokens * k * held / (experts * BLOCK)) + held
    assert blocks == {32: 8, 64: 6}[experts]
    h = jnp.asarray(rng.randn(tokens, d), dtype)
    w1 = jnp.asarray(rng.randn(held, d, width) / 4, dtype)
    w2 = jnp.asarray(rng.randn(held, width, d) / 8, dtype)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.ones(tokens, bool)
    chosen = _routing(rng, tokens, rows_of, held, offset, k)
    weigh = jnp.asarray(rng.randn(tokens, d), jnp.float32)

    out, counts = jax.jit(lambda *a: held_mix(*a, offset, experts))(h, chosen, gates, valid, w1, w2)
    assert counts["rows"].tolist() == list(rows_of)
    assert int(counts["passes"]) == passes and int(counts["slots"]) == passes * blocks * BLOCK
    _close(out, _by_expert(h, chosen, gates, valid, w1, w2, offset), dtype, "forward")

    def grads(fn):
        return jax.jit(jax.grad(
            lambda h, g, a, b: jnp.sum(fn(h, g, a, b).astype(jnp.float32) * weigh),
            argnums=(0, 1, 2, 3)))(h, gates, w1, w2)

    got = grads(lambda h, g, a, b: held_mix(h, chosen, g, valid, a, b, offset, experts)[0])
    want = grads(lambda h, g, a, b: _by_expert(h, chosen, g, valid, a, b, offset))
    for name, a, b in zip(("h", "gates", "w1", "w2"), got, want):
        assert a.dtype == b.dtype
        _close(a, b, dtype, "gradient of " + name)
    if 0 in rows_of:     # the expert with no row: its weights get no gradient
        empty = rows_of.index(0)
        assert not np.asarray(got[2][empty], np.float32).any()
        assert not np.asarray(got[3][empty], np.float32).any()
    if dtype == jnp.bfloat16 and passes == 1:
        monkeypatch.setattr(
            routed_experts, "_passes", lambda h, gates, w1, w2, route, blocks, block, gated:
            routed_experts._one_pass(h, gates, w1, w2, route, 0, blocks, block, gated))
        once = grads(lambda h, g, a, b: held_mix(h, chosen, g, valid, a, b, offset, experts)[0])
        assert bool((got[2] == once[2]).all()) and bool((got[3] == once[3]).all())


def test_the_work_is_the_buffers_whatever_the_routing():
    """Two routings of one shape lower to the same program, every block of
    the buffer has an expert in both (the experts' blocks are consecutive and
    add up to the buffer), and the slots computed are the same while the rows
    differ."""
    rng = np.random.RandomState(9)
    tokens, d, width, held, experts, k, offset = 640, 16, 64, 4, 32, 2, 8
    h = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(held, d, width) / 4, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(held, width, d) / 8, jnp.bfloat16)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.ones(tokens, bool)
    mix = jax.jit(lambda *a: held_mix(*a, offset, experts))
    blocks, texts, counted = row_buffer(tokens, k, held, experts, BLOCK)[0], [], []
    for rows_of in ((100, 100, 100, 100), (0, 3, 500, 129)):
        chosen = _routing(rng, tokens, rows_of, held, offset, k)
        texts.append(mix.lower(h, chosen, gates, valid, w1, w2).as_text())
        counted.append(jax.device_get(mix(h, chosen, gates, valid, w1, w2)[1]))
        padded = -(-np.asarray(rows_of) // BLOCK) * BLOCK
        owner = np.asarray(_owners(jnp.cumsum(jnp.asarray(padded)), 0, blocks, BLOCK))
        sizes = np.bincount(owner, minlength=held)
        assert sizes.sum() == blocks and (np.diff(owner) >= 0).all()
        # each expert has its padded rows' blocks, the last one the unfilled ones too
        assert (sizes[:-1] * BLOCK == padded[:-1]).all() and sizes[-1] * BLOCK >= padded[-1]
    assert texts[0] == texts[1]
    assert counted[0]["slots"] == counted[1]["slots"] == blocks * BLOCK
    assert counted[0]["passes"] == counted[1]["passes"] == 1
    assert counted[0]["rows"].sum() == 400 and counted[1]["rows"].sum() == 632


# the acting cell's shape cut down (granite_actor_b32: 32 rows a step, top-10 of 72, 36 held)
_FEW = dict(tokens=32, d=64, width=24, held=36, experts=72, k=10)


def _few_rows_routing(case):
    """chosen (32, 10) over 72 experts of which the first 36 are held."""
    rng, tokens, held, experts, k = np.random.RandomState(13), *(
        _FEW[key] for key in ("tokens", "held", "experts", "k"))
    if case == "cell":              # a router of the cell's kind: any ten of 72 a token
        picks = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif case == "one_expert":      # every pair on one held expert
        picks = np.full((tokens, k), 7)
    elif case == "all_held":        # every pair on held experts: the worst case
        picks = np.stack([rng.permutation(held)[:k] for _ in range(tokens)])
    else:                           # "none_held"
        picks = np.stack([held + rng.permutation(experts - held)[:k] for _ in range(tokens)])
    return jnp.asarray(picks, jnp.int32)


FEW_ROWS_CASES = ["cell", "one_expert", "all_held", "none_held"]


@pytest.mark.parametrize("tokens,k,experts,held,dtype,block,blocks", [
    (32, 10, 72, 36, jnp.bfloat16, 16, 56),      # granite_actor_b32's window: 896 slots, not 4,992
    (64, 10, 72, 36, jnp.bfloat16, 16, 76),      # its replay
    (32, 10, 72, 36, jnp.float32, 128, 39),      # float32 products copy a block's weights out
    (128, 10, 72, 36, jnp.bfloat16, 128, 46),    # 17.8 rows an expert: the MXU's tile
    (6144, 6, 128, 8, jnp.bfloat16, 128, 53),    # nemotron_twotower_train_t192's two parts
    (512, 6, 128, 8, jnp.bfloat16, 128, 12),
    (2, 6, 128, 8, jnp.bfloat16, 16, 9),
])
def test_a_blocks_height_follows_the_rows_an_expert_gets(tokens, k, experts, held, dtype, block,
                                                         blocks):
    """``block_rows`` reads shapes and dtype alone: 16 rows where a uniform
    router gives an expert fewer and the products are the kernel's, else 128;
    ``row_buffer`` counts its blocks in that height, and one pass of it
    covers every pair on held experts where the blocks are low."""
    assert (FEW_ROWS, BLOCK) == (16, 128)
    assert block_rows(tokens, k, experts, dtype) == block
    got, passes = row_buffer(tokens, k, held, experts, block)
    assert got == blocks
    assert passes * got * block >= tokens * min(k, held) and (passes == 1 or block == BLOCK)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", FEW_ROWS_CASES)
def test_held_mix_in_blocks_of_sixteen_is_held_mix_in_blocks_of_128(monkeypatch, case, dtype):
    """The layout moves no number: a row's products, its ``silu(a) b`` and a
    token's sum choice by choice are the same whichever slot the row lies
    in, forward and for every gradient: bit for bit in bfloat16 (the kernel
    in the interpreter), to float32's last digits through the block products
    (the CPU's ``dot`` sums a row in another order at another height); and
    ``counts["slots"]`` is the passes x blocks x rows of a block."""
    rng = np.random.RandomState(17)
    tokens, d, width, held, experts, k = (
        _FEW[key] for key in ("tokens", "d", "width", "held", "experts", "k"))
    h = jnp.asarray(rng.randn(tokens, d), dtype)
    w1 = jnp.asarray(rng.randn(held, d, 2 * width) / 8, dtype)
    w2 = jnp.asarray(rng.randn(held, width, d) / 5, dtype)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    valid = jnp.asarray(np.arange(tokens) != 5)
    chosen = _few_rows_routing(case)
    weigh = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    live = int(((np.asarray(chosen) < held) & np.asarray(valid)[:, None]).sum())

    def both(block):
        monkeypatch.setattr(routed_experts, "block_rows", lambda *a: block)
        mix = lambda h, g, a, b: held_mix(h, chosen, g, valid, a, b, 0, experts, True)  # noqa: E731
        out, counts = jax.jit(mix)(h, gates, w1, w2)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(mix(*a)[0].astype(jnp.float32) * weigh), argnums=(0, 1, 2, 3)))(
                h, gates, w1, w2)
        blocks, passes = row_buffer(tokens, k, held, experts, block)
        assert int(counts["rows"].sum()) == live
        assert int(counts["slots"]) == int(counts["passes"]) * blocks * block
        # the kernels in blocks of 128 run the blocks that hold a row; in blocks of 16, and the
        # plain products, every block
        skips = dtype == jnp.bfloat16 and block == BLOCK
        assert int(counts["blocks_run"]) == (
            int((-(-np.asarray(counts["rows"]) // block) * block).sum()) if skips
            else int(counts["slots"]))
        return out, grads, int(counts["passes"]), passes

    low, low_grads, low_passes, covers = both(FEW_ROWS)
    tall, tall_grads, _, _ = both(BLOCK)
    assert covers == 1 and low_passes == 1     # 56 blocks of 16 hold 320 rows on any 36 experts
    assert low.dtype == dtype and (np.asarray(low, np.float32).any() == (case != "none_held"))
    for name, a, b in zip(("out", "h", "gates", "w1", "w2"), (low, *low_grads), (tall, *tall_grads)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        tol = 0.0 if dtype == jnp.bfloat16 else 2e-6 * max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(a, b, rtol=0, atol=tol, err_msg=name)


@pytest.mark.parametrize("case", FEW_ROWS_CASES)
def test_a_block_of_sixteen_belongs_to_one_expert(case):
    """``_owners`` at 16 rows a block: non-decreasing, every block has an
    owner, and each held expert's rows lie in blocks that are his alone."""
    tokens, held, experts, k = (_FEW[key] for key in ("tokens", "held", "experts", "k"))
    chosen = np.asarray(_few_rows_routing(case))
    rows = np.bincount(chosen[chosen < held], minlength=held)
    padded = -(-rows // FEW_ROWS) * FEW_ROWS
    blocks = row_buffer(tokens, k, held, experts, FEW_ROWS)[0]
    assert padded.sum() <= blocks * FEW_ROWS       # one pass
    owner = np.asarray(_owners(jnp.cumsum(jnp.asarray(padded)), 0, blocks, FEW_ROWS))
    assert owner.shape == (blocks,) and (np.diff(owner) >= 0).all()
    assert owner.min() >= 0 and owner.max() < held
    base = np.cumsum(padded) - padded
    for e in np.flatnonzero(rows):
        mine = np.arange(base[e] // FEW_ROWS, (base[e] + padded[e]) // FEW_ROWS)
        assert (owner[mine] == e).all()
        assert (np.flatnonzero(owner == e)[:mine.size] == mine).all()   # and no block before them
    # an expert with no row has no block, but the last, who owns what no row fills
    assert not np.isin(owner, np.flatnonzero(rows[:-1] == 0)).any()


def test_the_net_counts_its_buffers_slots_and_the_passes_past_the_first():
    """``counter_buffer_slots`` and ``counter_expert_passes`` of a window:
    a router near uniform fills one pass of every buffer (0 passes past the
    first), one whose bias sends every token to two held experts outgrows
    the forward part's buffer once."""
    net = dict(NET, pattern="E", n_experts=32, top_k=2, experts_held=4, expert_offset=8)
    module = HybridNet(num_actions=5, **net)
    obs, _ = _window(2, rows=6, steps=108, observed=1.1)
    params = _params(module, obs)
    seen = jnp.ones((6, 108), jnp.float32)

    def counters(bias):
        mixer = dict(params["layer0"]["mixer"], score_bias=jnp.asarray(bias, jnp.float32))
        p = dict(params, layer0=dict(params["layer0"], mixer=mixer))
        return jax.device_get(module.apply(
            {"params": p}, obs, None, seq=True, key_mask=seen, burn_in=8)["counters"])

    sizes = [row_buffer(n, 2, 4, 32, BLOCK)[0] * BLOCK for n in (6 * 8, 6 * 100)]   # float32: 128
    plain = counters(np.zeros(32))
    assert plain["buffer_slots"] == sum(sizes) and plain["expert_passes"] == 0
    assert plain["slots_run"] == plain["buffer_slots"]      # float32: the plain products skip none
    assert 0 < plain["rows_held"] < 0.5 * 2 * 6 * 108
    skewed = counters(np.eye(32)[[9, 10]].sum(axis=0) * 10.0)
    assert skewed["rows_held"] == 2 * 6 * 108          # every choice of every token
    # the forward part's 1,200 rows in two experts' 640 slots each, a 896-slot buffer: a second pass
    assert skewed["expert_passes"] == 1 and skewed["buffer_slots"] == sizes[0] + 2 * sizes[1]


def test_every_product_of_the_gradient_sits_under_the_experts_scope():
    """Forward and backward: each product of the grouped kernel in the
    compiled gradient (on the CPU the interpreter's ``dot``s, the only ones
    ``held_mix`` has) carries ``experts`` as a component of its ``op_name``,
    as ``benchmark.trace_reduce.scopes_of`` reads a profile: the custom
    VJP's backward products inherit the scope their forward call was made
    under (else ``experts_roofline`` would time the forward products alone).
    tests/test_chip_compile.py reads the same off the kernel's calls in the
    program compiled for a v5e."""
    import re

    from benchmark import trace_reduce

    rng = np.random.RandomState(3)
    tokens, d, width, held, experts, k, offset = 64, 16, 32, 4, 32, 2, 8
    h = jnp.asarray(rng.randn(tokens, d), jnp.bfloat16)
    w1 = jnp.asarray(rng.randn(held, d, width), jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(held, width, d), jnp.bfloat16)
    gates = jnp.asarray(rng.rand(tokens, k), jnp.float32)
    chosen = _routing(rng, tokens, (20, 0, 30, 5), held, offset, k)
    loss = lambda h, g, a, b: jnp.sum(  # noqa: E731
        held_mix(h, chosen, g, jnp.ones(tokens, bool), a, b, offset, experts)[0].astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(h, gates, w1, w2).compile().as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if re.search(r"= \S+ dot\(", line)]
    # two forward products, their two rows' cotangents and two weight sums
    assert len(names) == 6
    outside = [n for n in names if trace_reduce.scopes_of(n, [EXPERTS_SCOPE]) != [EXPERTS_SCOPE]]
    assert not outside, outside


# -- the train step -----------------------------------------------------------


def test_train_step_counts_rows_and_records_its_layout(geister, tmp_path):
    from handyrl_tpu.utils import trace

    _, args, module, params, batch = geister
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    finally:
        trace.shutdown()
    layout = [r for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
              if r["name"] == "model.layout"]
    assert len(layout) == 1
    layout = [record["attrs"] for record in layout]
    assert layout[0]["pattern"] == "MEM*E" and layout[0]["experts_held"] == 4
    assert layout[0]["experts"] == 8 and layout[0]["params_mamba"] > 0
    trunk = sum(x.size for name, sub in params.items() if name.startswith("layer")
                for x in jax.tree.leaves(sub))
    assert sum(layout[0][k] for k in ("params_mamba", "params_attention", "params_experts")) == trunk

    state = ctx.init_state(params)
    state, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
    metrics = jax.device_get(metrics)
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    observed = float(np.sum(batch["observation_mask"]))
    # two routed layers, top-2 of 8 with 4 held: about half of the choices
    assert 0.2 * 2 * 2 * observed < metrics["counter_rows_held"] < 0.8 * 2 * 2 * observed
    assert metrics["counter_expert_rows_max"] >= metrics["counter_expert_rows_mean"] > 0
    assert metrics["counter_rows_held"] == pytest.approx(2 * 4 * metrics["counter_expert_rows_mean"])


def _primitives(jaxpr):
    """The name of every primitive in ``jaxpr`` and in the jaxprs its
    equations hold."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in sub_jaxprs(eqn):
            found |= _primitives(sub)
    return found


@pytest.mark.parametrize("name,env_args,train_args", [
    ("GeeseNet", {"env": "HungryGeese"}, {"turn_based_training": False}),
    ("TransformerNet", {"env": "Geister", "net": "transformer",
                        "net_args": {"d_model": 32, "n_heads": 2, "n_layers": 2, "memory_len": 8}},
     {"observation": True, "burn_in_steps": 2, "seq_attention": "einsum"}),
    ("HybridNet", _config()["env_args"], {"observation": True, "burn_in_steps": 2}),
], ids=["GeeseNet", "TransformerNet", "HybridNet"])
def test_the_update_is_straight_line_code_of_the_step(name, env_args, train_args):
    """No net's step holds a ``cond``, sentinel on or off: the update runs
    in the step's own computation and the verdict is a select on each leaf
    (a conditional fixes a layout per operand at its boundary and hides the
    clip's norm from the sentinel's: PERF.md, PR 38).  ``HybridNet``'s own
    ``while`` over the expert buffer's passes stays."""
    from benchmark import traffic

    cfg = normalize_args({"env_args": dict(env_args), "train_args": dict(
        train_args, batch_size=2, forward_steps=4, seed=3)})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(3)
    np.random.seed(3)
    env = make_env(args["env"])
    module = env.net()
    assert type(module).__name__ == name
    batch = traffic.random_play_batches(env, module, args, 1, 2)[0]
    params = jax.eval_shape(lambda: traffic.seeded_params(module, env, 3))
    for sentinel in (True, False):
        ctx = TrainContext(module, dict(args, sentinel=sentinel), make_mesh({"dp": 1}))
        state = {"params": params, "opt_state": jax.eval_shape(ctx.tx.init, params),
                 "steps": jax.ShapeDtypeStruct((), jnp.int32)}
        found = _primitives(jax.make_jaxpr(ctx._step_fn)(state, batch, jnp.float32(1e-5)).jaxpr)
        assert "cond" not in found, sorted(found)
        assert "select_n" in found and "dot_general" in found     # the walk saw the step
        assert ("while" in found) == (name == "HybridNet")


def test_a_mesh_other_than_dp_1_is_refused_by_name(geister):
    _, args, module, _, _ = geister
    with pytest.raises(ValueError, match=r"HybridNet trains on mesh \{'dp': 1\} only"):
        TrainContext(module, args, make_mesh({"dp": 2}))


def test_an_unknown_layer_kind_is_refused():
    module = HybridNet(num_actions=3, pattern="MX")
    with pytest.raises(ValueError, match="a layer is one of"):
        module.init(jax.random.PRNGKey(0), {"a": jnp.zeros((1, 4))}, None)


def test_step_mode_acts_through_the_inference_model():
    from handyrl_tpu.models import InferenceModel, init_variables

    env = make_env({"env": "TicTacToe", "net": "hybrid", "net_args": dict(NET, memory_len=4)})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env))
    env.reset()
    hidden = model.init_hidden()
    first = model.inference(env.observation(0), hidden)
    assert first["policy"].shape == (9,) and float(first["hidden"]["pos"]) == 1.0
    env.play(4)
    again = model.inference(env.observation(0), first["hidden"])
    fresh = model.inference(env.observation(0), hidden)
    assert not np.allclose(again["policy"], fresh["policy"], atol=1e-5)   # the state matters


# -- the count of its work ----------------------------------------------------


def test_flops_of_the_published_cell_against_a_hand_count():
    with open(os.path.join(REPO, "benchmark", "configs", "nemotron_twotower_30b_a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "workloads", "nemotron_twotower_train_t192.json")) as f:
        cell = json.load(f)
    work = FLOPS.train_update(config, cell)
    # parameters, by hand: the issue's arithmetic
    mamba = 2688 + 2688 * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * 2688
    attention = 2688 + 2 * 2688 * 128 * 34
    experts = 2688 + 2688 * 128 + 128 + 2 * 2688 * 3712 + 2 * 8 * 2688 * 1856
    rest = 270 * 2688 + 2688 + 2688 * 2688 + 2688 + 2688 + 2689 * 216
    assert work["parameters"] == 4 * mamba + attention + 4 * experts + rest == 587_420_376
    # a token is a step that carries an observation: 0.413 of the 184 forward
    # steps, 0.127 of the 8 burn-in steps (the configuration's shapes)
    trained, burn = 64 * 184 * 0.413, 64 * 8 * 0.127
    assert work["tokens"] == pytest.approx(trained + burn)
    # multiply-adds a token, by hand
    ssd = 64.5 * (8 * 128 + 64 * 64) + 2 * 64 * 64 * 128
    m = 2688 * 10304 + 4096 * 2688 + 4 * 6144 + ssd
    e = 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 / 128 * 2 * 2688 * 1856
    a = 2 * 2688 * 128 * 34 + 2 * ((184 * 0.413 + 8 * 0.127 + 1) / 2) * 32 * 128
    per_token = 270 * 2688 + 2688 * 2688 + 2688 * 216 + 4 * m + 4 * e + a
    assert work["flops"] == pytest.approx(2 * per_token * (3 * trained + burn))
    # the issue's 22 TFLOP an update counts all 12,288 steps as tokens
    assert 20e12 < work["flops"] / 0.413 < 24e12
    scopes = FLOPS.scope_work(config, cell)
    assert scopes["experts"]["rows"] == pytest.approx(4 * (trained + burn) * 6 * 8 / 128)
    assert scopes["experts"]["flops"] == pytest.approx(
        scopes["experts"]["rows"] * 3 * 2 * 2 * 2688 * 1856)
    assert scopes["ssd"]["flops"] == pytest.approx(2 * 4 * ssd * (3 * trained + burn))
    assert sum(s["flops"] for s in scopes.values()) < work["flops"]
