"""The dp gradient summed by the net's sections (``parallel/mesh.py``
``sum_section_grads``: a ring for the large leaves, a psum for the rest) on
the 8-device virtual CPU mesh: a step on {dp: 4} and {dp: 8} against {dp: 1},
the ring's sum in any order, which nets sum their own, what ``put_batch``
packs, and the counts the context reports.  Cut from tests/test_parallel.py
(PR 67), which keeps ring attention, the mp mesh and the bfloat16 step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.parallel import make_mesh
from nets import _env_batch

_SYNC_NETS = {
    # d256: mlp_up/mlp_dn are 2^18 elements, so four leaves ride the ring
    # and the attention kernels (2^16) take the psum
    "transformer": (
        {"env": "TicTacToe", "net": "transformer",
         "net_args": {"d_model": 256, "n_heads": 4, "n_layers": 2}},
        {"observation": True, "burn_in_steps": 2},
        {"ring_leaves": 4, "psum_leaves": 38},
    ),
    # GeeseNet has no sections: GSPMD sums its gradient, as on any mesh
    "geesenet": ({"env": "HungryGeese"}, {}, None),
}


def _one_step(module, variables, batch, args, mesh_spec, lr):
    from handyrl_tpu.parallel import TrainContext

    ctx = TrainContext(module, args, make_mesh(mesh_spec))
    state = ctx.init_state(variables["params"])
    state, metrics = ctx.train_step(state, ctx.put_batch(batch), lr)
    return ctx, state, jax.device_get(metrics)


def _param_diff(a, b):
    return np.concatenate([
        np.abs(np.asarray(x) - np.asarray(y)).ravel()
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    ])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("net", sorted(_SYNC_NETS))
def test_dp_step_sums_its_own_gradient(net, dtype, monkeypatch):
    """One step on {dp: 4} and {dp: 8} against {dp: 1} on the same batch,
    held to what chip_smoke.py's phase_dp_train_step holds; the ring
    against the all-psum form of the same step far inside that; updated
    parameters the same bits on every device."""
    from handyrl_tpu.parallel import mesh as mesh_mod

    env_args, overrides, want = _SYNC_NETS[net]
    module, variables, batch, args = _env_batch(env_args, {**overrides, "compute_dtype": dtype})
    lr = 1e-4
    ctx1, state1, m1 = _one_step(module, variables, batch, args, {"dp": 1}, lr)
    assert ctx1.grad_sync is None  # one device: the program it always was
    for dp in (4, 8):
        ctx, state, m = _one_step(module, variables, batch, args, {"dp": dp}, lr)
        assert (ctx.grad_sync and {k: ctx.grad_sync[k] for k in want}) == want
        for x in jax.tree.leaves(state["params"]):
            shards = [np.asarray(s.data) for s in x.addressable_shards]
            assert len(shards) == dp
            assert all((s == shards[0]).all() for s in shards[1:]), "replicas differ"
        assert m["dcnt"] == m1["dcnt"]
        for k in m1:
            np.testing.assert_allclose(m[k], m1[k], rtol=1e-3, atol=1e-5, err_msg=k)
        # Adam's first update is lr * g / (|g| + eps): a gradient that is
        # noise around zero may land on either side, 2 lr apart
        diff = _param_diff(state["params"], state1["params"])
        assert diff.max() <= 2.1 * lr
        assert (diff > 0.1 * lr).mean() < 1e-2
        if not want:
            continue
        with monkeypatch.context() as patch:
            patch.setattr(mesh_mod, "RING_MIN_ELEMENTS", 1 << 62)
            ctx_p, state_p, m_p = _one_step(module, variables, batch, args, {"dp": dp}, lr)
        assert ctx_p.grad_sync["ring_leaves"] == 0
        np.testing.assert_allclose(m_p["total"], m["total"], rtol=1e-6)
        diff = _param_diff(state["params"], state_p["params"])
        assert (diff > 0.1 * lr).mean() < (1e-5 if dtype == "float32" else 2e-3)


@pytest.mark.parametrize(
    "order", [[0, 1, 2, 3], [0, 1, 3, 2], [3, 1, 4, 0, 7, 6, 2, 5]], ids=str
)
@pytest.mark.parametrize("rows", [8, 5, 1])  # per chunk: even, odd, no halves to send both ways
def test_ring_sum_any_order(order, rows):
    """The ring's sum is the sum, with the same bits on every chip, whatever
    order it visits the chips in."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from handyrl_tpu.parallel.mesh import _ring_sum

    n = len(order)
    x = jax.random.normal(jax.random.PRNGKey(n), (n, n, rows, 24), jnp.float32)
    fn = shard_map(
        lambda a: _ring_sum(a[0], "dp", order)[None], mesh=make_mesh({"dp": n}),
        in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
    )
    y = np.asarray(jax.jit(fn)(x))
    np.testing.assert_allclose(y[0], np.asarray(x).sum(0), rtol=1e-5, atol=1e-5)
    assert all((y[i] == y[0]).all() for i in range(n))


def test_sum_grads_picks_the_way_by_shape():
    """Large leaves with a leading dimension dp divides ride the ring (those
    of one row shape in one buffer); a leading dimension it does not
    divide, a vector or a small leaf takes the psum.  Either way the sum
    is the sum, in the leaf's own shape and dtype."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from handyrl_tpu.parallel.mesh import (
        RING_MIN_ELEMENTS, grad_sync_axis, grad_sync_counts, ring_order, sum_grads,
    )

    n, wide = 4, RING_MIN_ELEMENTS // 1024
    shapes = {
        "rides": ((1024, wide), jnp.bfloat16),
        "rides_too": ((2048, wide), jnp.bfloat16),       # same rows' shape: same buffer
        "rides_alone": ((1024, 2 * wide), jnp.float32),
        "odd_rows": ((1023, 512), jnp.bfloat16),
        "vector": ((RING_MIN_ELEMENTS,), jnp.float32),
        "small": ((64, 64), jnp.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    tree = {
        name: jax.random.normal(k, (n,) + shape, jnp.float32).astype(dtype)
        for k, (name, (shape, dtype)) in zip(keys, shapes.items())
    }
    one_chip = jax.tree.map(lambda x: x[0], tree)
    assert grad_sync_counts(one_chip, n) == {
        "ring_leaves": 3, "ring_bytes": (2 + 4 + 8) * RING_MIN_ELEMENTS,
        "psum_leaves": 3,
        "psum_bytes": 1023 * 512 * 2 + 4 * RING_MIN_ELEMENTS + 64 * 64 * 4,
    }

    mesh = make_mesh({"dp": n})
    fn = shard_map(
        lambda t: jax.tree.map(
            lambda x: x[None],
            sum_grads(jax.tree.map(lambda x: x[0], t), "dp", ring_order(mesh)),
        ),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False,
    )
    text = jax.jit(fn).lower(tree).as_text()
    assert text.count("collective_permute") == 2 * 2 * 2 * (n - 1)  # buffers, ways, rounds
    out = jax.jit(fn)(tree)
    for name, x in tree.items():
        got = np.asarray(out[name].astype(jnp.float32))
        assert out[name].dtype == x.dtype and got.shape == x.shape
        want = np.asarray(x.astype(jnp.float32)).sum(0)
        tol = 1e-5 if x.dtype == jnp.float32 else 4e-2
        np.testing.assert_allclose(got[0], want, rtol=tol, atol=tol, err_msg=name)
        assert all((got[i] == got[0]).all() for i in range(n)), name

    assert grad_sync_axis(make_mesh({"dp": 4})) == "dp"
    assert grad_sync_axis(make_mesh({"dp": 1})) is None
    assert grad_sync_axis(make_mesh({"dp": 2, "mp": 2})) is None
    assert grad_sync_axis(make_mesh({"dp": 2, "sp": 4})) is None
    assert grad_sync_axis(make_mesh({"dp": 4, "mp": 1})) == "dp"


def test_second_axis_keeps_the_inferred_collectives():
    """On {dp: 2, mp: 2} the gradient's collectives are not a plain sum
    over one axis: the step lowers as before, with nothing from grad_sync."""
    from handyrl_tpu.parallel import TrainContext

    env_args, overrides, _ = _SYNC_NETS["transformer"]
    module, variables, batch, args = _env_batch(env_args, overrides)
    ctx = TrainContext(module, args, make_mesh({"dp": 2, "mp": 2}))
    state = ctx.init_state(variables["params"])
    text = ctx._bind(state).lower(state, ctx.put_batch(batch), jnp.float32(1e-4)).as_text()
    assert ctx.grad_sync is None
    for word in ("collective_permute", "shard_map", "manual"):
        assert word not in text, word


@pytest.mark.parametrize("net,seq_forward,own", [
    ("transformer", True, True), ("transformer", False, False), ("geesenet", True, False),
])
def test_only_a_sectioned_whole_window_net_sums_its_own(net, seq_forward, own):
    """One predicate decides both who enters the shard_map and which path
    forward_prediction takes; a sum_grads that no section would call is
    refused, not dropped (the gradient would go unsummed)."""
    from handyrl_tpu.parallel import TrainContext, forward_prediction
    from handyrl_tpu.parallel.train_step import sums_own_grads

    env_args, overrides, _ = _SYNC_NETS[net]
    module, variables, batch, args = _env_batch(env_args, {**overrides, "seq_forward": seq_forward})
    assert sums_own_grads(module, args) == own
    ctx = TrainContext(module, args, make_mesh({"dp": 4}))
    state = ctx.init_state(variables["params"])
    text = ctx._bind(state).lower(state, ctx.put_batch(batch), jnp.float32(1e-4)).as_text()
    assert (ctx.grad_sync is not None) == own
    assert ("collective_permute" in text) == own
    if not own:
        with pytest.raises(ValueError, match="sum_grads"):
            forward_prediction(
                module, variables["params"], jax.tree.map(jnp.asarray, batch), args,
                lambda grads, x_ct, token: (grads, x_ct, token),
            )
    with pytest.raises(TypeError):      # not a field: net_args cannot switch it
        type(module)(**{**env_args.get("net_args", {}), "sums_own_grads": False})


@pytest.mark.parametrize("env_args,overrides", [
    _SYNC_NETS["transformer"][:2],
    _SYNC_NETS["geesenet"][:2],
    ({"env": "Geister"}, {"observation": True, "burn_in_steps": 2}),      # DRC: a scan of steps
], ids=["transformer", "geesenet", "drc"])
def test_put_batch_packs_nothing_for_a_net_that_takes_no_packed_order(env_args, overrides):
    """Only a net whose whole-window call takes ``packed_order`` gets the
    leaf: these three get the batch they always got, and the step lowers to
    the text it lowers to from the host batch laid out by hand."""
    from handyrl_tpu.parallel import TrainContext
    from handyrl_tpu.parallel.train_step import PACKED_ORDER, takes_packed_order

    module, variables, batch, args = _env_batch(env_args, {**overrides, "forward_steps": 40})
    assert not takes_packed_order(module, args)
    ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    if env_args["env"] == "Geister":    # a row observes every second step: a packing net's leaf is 32 long
        assert (batch["observation_mask"][..., 0] > 0).sum(axis=1).max() <= 32
    put, stacked = ctx.put_batch(batch), ctx.put_batches([batch, batch])
    assert PACKED_ORDER not in put and PACKED_ORDER not in stacked and ctx._packed_bounds == {}
    by_hand = jax.device_put(ctx._compact_ff(batch), ctx._batch_shard)
    assert jax.tree.structure(put) == jax.tree.structure(by_hand)
    assert [x.shape for x in jax.tree.leaves(put)] == [x.shape for x in jax.tree.leaves(by_hand)]
    state = ctx.init_state(variables["params"])
    step = ctx._bind(state)
    assert step.lower(state, put, jnp.float32(1e-4)).as_text() \
        == step.lower(state, by_hand, jnp.float32(1e-4)).as_text()


def test_grad_sync_event_reports_the_context_counts(tmp_path):
    from handyrl_tpu.utils import trace as trace_mod

    env_args, overrides, _ = _SYNC_NETS["transformer"]
    module, variables, batch, args = _env_batch(env_args, overrides)
    path = str(tmp_path / "trace.jsonl")
    assert trace_mod.configure({"enabled": True, "path": path})
    try:
        ctx, state, _ = _one_step(module, variables, batch, args, {"dp": 4}, 1e-4)
        ctx.train_step(state, ctx.put_batch(batch), 1e-4)  # bound already: no second event
    finally:
        trace_mod.shutdown()
    events = [r for r in trace_mod.read_trace(path) if r["name"] == "train.grad_sync"]
    assert len(events) == 1
    assert {k: events[0]["attrs"][k] for k in ctx.grad_sync} == ctx.grad_sync


def test_flops_per_step_counts_every_chip_under_grad_sync():
    """The shard_map body holds one chip's rows; a step's flops are the
    whole batch's, as on one device."""
    from handyrl_tpu.parallel import TrainContext
    from handyrl_tpu.parallel.train_step import jaxpr_flops

    env_args, overrides, _ = _SYNC_NETS["transformer"]
    module, variables, batch, args = _env_batch(env_args, overrides)
    counts = {}
    for dp in (1, 4):
        ctx = TrainContext(module, args, make_mesh({"dp": dp}))
        state = ctx.init_state(variables["params"])
        device_batch = ctx.put_batch(batch)
        jaxpr = jax.make_jaxpr(ctx._step_fn)(state, device_batch, jnp.float32(1e-5))
        counts[dp] = (ctx.flops_per_step(state, device_batch), jaxpr_flops(jaxpr.jaxpr))
    # the jaxpr of the encoder's section also holds the observation's
    # cotangent, which nothing reads (XLA drops it)
    np.testing.assert_allclose(counts[4][1], counts[1][1], rtol=5e-3)
    # the HLO count also holds the ring's adds and selects, which at this
    # batch (a few rows a chip) are a tenth of the matmuls; without the
    # factor for the chips it would be a quarter
    np.testing.assert_allclose(counts[4][0], counts[1][0], rtol=0.15)
