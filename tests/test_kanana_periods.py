"""``HybridNet``'s ``kanana_2_30b_a3b`` family where its stack is deep enough
to scan and where eight chips share a layer (the net of
tests/test_kanana_net.py, from which PR 67 cut this file): the periods
behind the leading layers as a ``lax.scan`` against the unrolled stack, in
float32 against the reference and in bfloat16 (the grouped kernel in the
Pallas interpreter) gradient by gradient; and the eight shares of the
eight-chip deployment, this family's and ``trinity_mini``'s, adding up to
the uncut reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import nets
from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import ExpertLayer, LatentAttention, Layer
from nets import KANANA, _apart, _bf16_loss_and_grads, _inputs, _load, _window

NET = KANANA.net
SLOT = NET["kv_latent"] + NET["qk_rope_dim"]        # what a ring keeps of a step
REFERENCE = KANANA.REFERENCE
_module, _init, _reference = (functools.partial(f, KANANA) for f in (
    nets._module, nets._init, nets._reference))
# bfloat16 weights and stream: sound, and weights rounded to 8 bits first
BF16_TOLERANCE = 0.05


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
    obs, mask = _inputs(KANANA)
    assert hybrid._periods("L-LELELE") == (2, "LE") and hybrid._periods("L-LELE") == (6, "")
    assert hybrid._periods("CECECE") == (0, "CE") and hybrid._periods("MEMEM*EME") == (9, "")
    got = _window(module, params, obs, mask, burn_in=4, remat=remat)
    want = _reference(params, obs, mask, choices=got["choices"], pattern="L-LELELE")
    assert _apart(got, want, mask) < 2e-5 and sorted(got["choices"]) == ["layer3", "layer5", "layer7"]
    (value, counters), grads = _bf16_loss_and_grads(module, params, obs, mask, remat, 4)
    assert float(counters["expert_stack_reads"]) == 6       # three periods, two window parts
    monkeypatch.setattr(hybrid, "_periods", lambda pattern: (len(pattern), ""))     # unrolled
    (want, unrolled), want_grads = _bf16_loss_and_grads(module, params, obs, mask, remat, 4)
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
