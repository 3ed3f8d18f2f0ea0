"""What the ``E`` layers of ``HybridNet``'s ``zaya1_8b`` family read (the net
of tests/test_zaya_net.py, from which PR 67 cut this file): the scan over
periods that reads the stacked experts in place against the unrolled stack in
bfloat16 (the grouped kernel in the Pallas interpreter), what reads no stack
in place, and the two shares of the two-chip deployment."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import ExpertLayer, HybridNet
from nets import ZAYA, _bf16_loss_and_grads, _window

NET = ZAYA.net
REFERENCE = ZAYA.REFERENCE
ROWS = ZAYA.rows
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.06


@pytest.fixture(scope="module")
def toy():
    return nets._toy(ZAYA)


# -- the stacked experts, read where they lie -----------------------------------


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
    out = _window(module, params, obs, mask, burn_in=4)
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
