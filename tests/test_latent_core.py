"""ops/latent_core.py: a window part's latent attention core as one
whole-row Pallas kernel (here the Pallas interpreter, at the smallest widths
that cross a tile's edge), against the einsum lines of models/hybrid.py
``LatentAttention`` on equal operands: the output and the gradients of q, kv
and kr; which operands take which path and why; the one-off event that says
so; and the count that guards the set-up budget: the kernels' bodies hold
as many equations at eight heads as at two.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.models.hybrid import NEG_INF, LatentAttention, _rope_pairs, _seen_from
from handyrl_tpu.ops import attention_core, latent_core
from handyrl_tpu.parallel import TrainContext
from handyrl_tpu.parallel.train_step import sub_jaxprs
from handyrl_tpu.utils import trace

DN, DR, DV = 128, 64, 128       # the published widths: an odd head begins half a tile in
LENGTH, PAST, MEMORY, THETA = 16, 8, 12, 1e4


def einsum_lines(q, kv, kr, before, count, static):
    """What ``LatentAttention._attend`` computes in window mode without the
    kernel, on the kernel's flat operands (the part's own keys before the
    past's): ``_rope_pairs``, ``_seen_from``'s mask, three einsums."""
    Dn, Dr, Dv, memory_len, theta = static
    (n, length), keys = q.shape[:2], kv.shape[1]
    heads, past = q.shape[2] // (Dn + Dr), keys - length
    valid = jnp.arange(length)[None, :] < count[:, None]
    at = before[:, None] + jnp.arange(length)[None, :]
    q = q.reshape(n, length, heads, Dn + Dr)
    qn, qr = q[..., :Dn], _rope_pairs(q[..., Dn:], at, theta).astype(q.dtype)
    kv = kv.reshape(n, keys, heads, Dn + Dv)
    seen = _seen_from(before, past, valid, memory_len)                      # the past's first
    allowed = jnp.concatenate([seen[..., past:], seen[..., :past]], axis=-1)
    scores = (jnp.einsum("nqhd,nkhd->nhqk", qn, kv[..., :Dn], preferred_element_type=jnp.float32)
              + jnp.einsum("nqhr,nkr->nhqk", qr, kr, preferred_element_type=jnp.float32)
              ) * (Dn + Dr) ** -0.5
    weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, NEG_INF), axis=-1).astype(q.dtype)
    return jnp.einsum("nhqk,nkhd->nqhd", weights, kv[..., Dn:]).reshape(n, length, heads * Dv)


def _operands(seed, dtype, n, length, past, heads, widths=(DN, DR, DV)):
    Dn, Dr, Dv = widths
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32).astype(dtype)  # noqa: E731
    return (draw(keys[0], n, length, heads * (Dn + Dr)),
            draw(keys[1], n, length + past, heads * (Dn + Dv)),
            draw(keys[2], n, length + past, Dr)), draw(keys[3], n, length, heads * Dv)


def _far(a, b):
    """Largest difference of two arrays over the larger's scale."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max(initial=0.0) / max(np.abs(b).max(initial=0.0), 1.0))


# rows: no valid key and nothing before; the part full behind a full past;
# ``count`` short of the part behind a short past.  memory_len 12 is shorter
# than the 16 + 8 keys in reach: the oldest fall out of the later queries' sight
@pytest.mark.parametrize("dtype,past,widths,close", [
    (jnp.bfloat16, PAST, (DN, DR, DV), (1e-2, 2e-2)),       # the einsum path's own tolerance
    (jnp.bfloat16, 0, (DN, DR, DV), (1e-2, 2e-2)),
    (jnp.float32, PAST, (DN, DR, DV), (2e-6, 5e-6)),        # the same arithmetic, tightly
    (jnp.float32, PAST, (DN, 128, 256), (2e-6, 5e-6)),      # a head a block: a rotated part of 128
], ids=["bf16_past8", "bf16_no_past", "f32_past8", "f32_rope128"])
def test_kernel_matches_the_einsum_lines_and_their_gradients(dtype, past, widths, close):
    heads = 3 if widths[1] == 128 else 2 if dtype == jnp.bfloat16 else 4
    static = widths + (MEMORY, THETA)
    operands, w_out = _operands(past + heads, dtype, 3, LENGTH, past, heads, widths)
    before = jnp.asarray([0, past, min(past, 3)], jnp.int32)
    count = jnp.asarray([0, LENGTH, 5], jnp.int32)
    loss = lambda core: lambda *x: (    # noqa: E731
        core(*x, before, count, static).astype(jnp.float32) * w_out).sum()
    got = latent_core.latent_core(*operands, before, count, static)
    want = einsum_lines(*operands, before, count, static)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all()) and _far(got, want) < close[0]
    # the row with no valid key: the uniform mix of every key's values
    values = operands[1][0].astype(jnp.float32).reshape(LENGTH + past, heads, -1)[..., widths[0]:]
    assert _far(got[0], jnp.broadcast_to(values.mean(axis=0).reshape(-1), got[0].shape)) < 2e-2
    grads = jax.grad(loss(latent_core.latent_core), argnums=(0, 1, 2))(*operands)
    wants = jax.grad(loss(einsum_lines), argnums=(0, 1, 2))(*operands)
    for name, a, b in zip(("q", "kv", "kr"), grads, wants):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        assert _far(a, b) < close[1], (name, _far(a, b))
    # nothing flows to a key no query sees: row 0's, and past ``count`` or ``before`` in row 2
    d_kr = np.abs(np.asarray(grads[2], np.float32)).max(axis=-1)
    assert d_kr[0].max() == 0.0 and d_kr[2, 5:LENGTH].max() == 0.0 and d_kr[2, :5].min() > 0.0
    if past:
        assert d_kr[2, LENGTH + 3:].max() == 0.0 and d_kr[2, LENGTH:LENGTH + 3].min() > 0.0


def test_a_short_memory_hides_old_keys_from_late_queries():
    """With ``memory_len`` 4 a query sees itself and three keys back: another
    key further back than that changes no output of the kernel's."""
    static = (DN, DR, DV, 4, THETA)
    (q, kv, kr), _ = _operands(7, jnp.float32, 2, LENGTH, PAST, 2)
    before, count = jnp.asarray([PAST, PAST], jnp.int32), jnp.asarray([LENGTH, LENGTH], jnp.int32)
    got = latent_core.latent_core(q, kv, kr, before, count, static)
    # own keys 0..7 and the whole past are four or more behind queries 11 on
    moved = latent_core.latent_core(q, kv.at[:, :8].add(3.0).at[:, LENGTH:].add(-2.0),
                                    kr.at[:, :8].add(1.0), before, count, static)
    assert _far(got[:, 11:], moved[:, 11:]) == 0.0 and _far(got[:, :8], moved[:, :8]) > 1e-3


@pytest.mark.parametrize("operands,why", [
    ((jnp.float32, 64, 8, 4, 128, 64, 128), "operands are float32, not bfloat16"),
    ((jnp.bfloat16, 64, 8, 4, 192, 32, 128), "qk_nope 192 and v_head 128 are not whole tiles"),
    ((jnp.bfloat16, 64, 8, 3, 128, 64, 128), "3 heads of qk_rope 64 end inside a tile"),
    ((jnp.bfloat16, 8, 0, 4, 128, 64, 128), "8 queries a row, under 64"),
    ((jnp.bfloat16, 64, 4, 4, 128, 64, 128), "64 queries behind 4 keys are not whole tiles of 8"),
    ((jnp.bfloat16, 1024, 8, 32, 128, 64, 128), "bytes of VMEM, over"),
    ((jnp.bfloat16, 96, 8, 32, 128, 64, 128), ""),      # the cell's forward part
], ids=["float32", "nope_192", "odd_heads", "burn_in", "past_4", "vmem", "the_cell"])
def test_fits_refuses_by_name_and_the_event_says_what_it_chose(operands, why, tmp_path):
    """``fits`` decides from dtype and shape, keeps what it decided and why
    under a key that names the kind, and a context with a tracer on writes
    it out once as ``model.attention_path``."""
    attention_core.PATHS.clear()
    assert latent_core.fits(*operands) == (not why)
    ((key, record),) = attention_core.PATHS.items()
    assert key[0] == record["kind"] == "L" and why in record["why"], record
    assert record["path"] == ("einsum" if why else "kernel")
    context = types.SimpleNamespace(_attention_paths=set())
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        TrainContext._record_attention_paths(context)
        TrainContext._record_attention_paths(context)       # said once
    finally:
        trace.shutdown()
    (event,) = [r["attrs"] for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
                if r["name"] == "model.attention_path"]
    assert event["plane"] == "learner" and event["path"] == record["path"]
    assert [event[k] for k in ("queries", "past", "heads", "qk_nope", "qk_rope", "v_head")] == list(
        operands[1:])
    attention_core.PATHS.clear()


def _kernel_equations(heads):
    """Equations in the forward and backward kernels' bodies, loops' and all."""
    def count(jaxpr):
        return sum(1 + sum(count(inner) for inner in sub_jaxprs(eqn)) for eqn in jaxpr.eqns)

    def body(fn, *operands):
        jaxpr = jax.make_jaxpr(fn)(*operands).jaxpr
        calls = [eqn for eqn in _walk(jaxpr) if eqn.primitive.name == "pallas_call"]
        assert len(calls) == 1
        return count(calls[0].params["jaxpr"])

    static = (DN, DR, DV, MEMORY, THETA)
    (q, kv, kr), d_out = _operands(0, jnp.bfloat16, 2, LENGTH, PAST, heads)
    before = count_ = jnp.zeros((2,), jnp.int32)
    return (body(lambda *x: latent_core._forward(*x, static, True), q, kv, kr, before, count_),
            body(lambda *x: latent_core._backward(*x, static, True), q, kv, kr, before, count_,
                 d_out))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in sub_jaxprs(eqn):
                yield from _walk(inner)


def test_the_kernels_bodies_do_not_grow_with_the_heads():
    """What guards the set-up budget (PERF.md, PRs 53 and 56): a body traced
    a head at a time is traced and lowered as thousands of equations at 32
    heads; these are a loop over column blocks, the same count at 2 and at 8."""
    two, eight = _kernel_equations(2), _kernel_equations(8)
    assert two == eight and all(0 < n < 400 for n in two), (two, eight)


def test_the_module_keeps_the_same_state_and_gradients_on_either_path(monkeypatch):
    """``LatentAttention`` in window mode in bfloat16 at the shortest part the
    kernel takes: what it returns, the latents it hands on and the gradient of
    its parameters, input and past, through the kernel and, with ``fits``
    answering no, through the einsum lines; step mode never takes the kernel."""
    length, n, C = attention_core.ROWS_MIN, 2, 32
    module = LatentAttention(32, 2, DN, DR, DV, C, 40, THETA, 1e-6)
    rng = jax.random.split(jax.random.PRNGKey(2), 3)
    h = jax.random.normal(rng[0], (n, length, 32), jnp.bfloat16)
    past = jax.random.normal(rng[1], (n, PAST, C + DR), jnp.float32)
    before = jnp.asarray([PAST, 5], jnp.int32)
    valid = jnp.arange(length)[None, :] < jnp.asarray([length, 23])[:, None]
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          module.init(rng[2], h, {"latent": past, "n": before}, valid))

    def run(params, h, past):
        out, new = module.apply(params, h, {"latent": past, "n": before}, valid)
        return (out.astype(jnp.float32) ** 2).sum(), (out, new)

    both = []
    for kernel in (True, False):
        if not kernel:
            monkeypatch.setattr(latent_core, "fits", lambda *a: False)
        jaxpr = jax.make_jaxpr(lambda p: run(p, h, past)[0])(params).jaxpr
        assert any(e.primitive.name == "pallas_call" for e in _walk(jaxpr)) == kernel
        both.append(jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2), has_aux=True))(
            params, h, past))
    ((_, (out, new)), grads), ((_, (out_e, new_e)), grads_e) = both
    assert new["latent"].shape == (n, PAST + length, C + DR) and list(new["n"]) == [PAST + length, 28]
    assert _far(new["latent"], new_e["latent"]) == 0.0 and list(new_e["n"]) == list(new["n"])
    assert _far(out, out_e) < 1e-2
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(grads_e)):
        assert a.dtype == b.dtype and _far(a, b) < 2e-2, (path, _far(a, b))
    ring = {"latent": jnp.zeros((n, 40, C + DR)), "pos": jnp.zeros((n,))}
    stepped = jax.make_jaxpr(lambda p: module.apply(p, h[:, 0], ring)[0])(params).jaxpr
    assert not any(e.primitive.name == "pallas_call" for e in _walk(stepped))
