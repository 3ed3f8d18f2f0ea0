"""``HybridNet``'s window packed to a bound the host read from the batch
(``parallel/train_step.py`` ``pack_order``, ``TrainContext.put_batch``): heads,
choices, counters, gradients and the states handed on equal the whole
window's, on Geister windows of 40 forward steps (the net of
tests/test_hybrid_net.py, from which PR 67 cut this file).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import (
    PACK_MULTIPLE, PACKED_ORDER, forward_prediction, pack_order, trim_burn_in)
from nets import HYBRID, SCAN, _geister, _predict

NET = HYBRID.net


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
            HYBRID, {"batch_size": 3, "burn_in_steps": burn_in, "forward_steps": 40})
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


_BOTH = {}


def _forward_and_gradient(module, args):
    """(params, batch) -> (forward_prediction's outputs, every parameter's
    gradient of a sum over the heads), one jitted call; the fixture's windows
    differ in their burn-in steps alone, so one program a count of those
    (and a shape of ``packed_order``)."""
    def loss(p, b):
        out = forward_prediction(module, p, b, args)
        return sum(jnp.sum(jnp.square(jnp.where(jnp.abs(out[k]) < 1e6, out[k], 0.0)))
                   for k in ("policy", "value", "return")), out

    return _BOTH.setdefault((module, args["burn_in_steps"]), jax.jit(
        lambda p, b: jax.grad(loss, has_aux=True)(p, b)[::-1]))


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
    window = _predict(module, args)(params, packed)
    scan = _predict(module, args, **SCAN)(params, batch)
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

    @jax.jit
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

