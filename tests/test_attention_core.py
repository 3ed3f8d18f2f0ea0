"""ops/attention_core.py: a window part's attention core as one whole-row
Pallas kernel (here the Pallas interpreter, at tiny sizes), against the
einsum lines of models/hybrid.py ``GroupedQueryAttention`` on equal
operands: the output, the rotated keys and every gradient; which operands
take which path; and the one-off event that says so.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models.hybrid import NEG_INF, GroupedQueryAttention, _rope
from handyrl_tpu.ops import attention_core
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import sub_jaxprs

D = 128     # the kernel's heads are whole 128-lane tiles


def einsum_lines(q, k, v, past_k, past_v, before, count, static):
    """What ``GroupedQueryAttention._attend`` computes in window mode
    without the kernel, on the kernel's flat operands: ``_rope``, the
    concatenated keys, the mask from positions, two einsums."""
    group, head_dim, memory_len, theta = static
    (n, length), Hk, past = q.shape[:2], k.shape[2] // head_dim, past_k.shape[1]
    q = q.reshape(n, length, Hk, group, head_dim)
    k, v = k.reshape(n, length, Hk, head_dim), v.reshape(n, length, Hk, head_dim)
    past_k, past_v = (x.reshape(n, past, Hk, head_dim) for x in (past_k, past_v))
    valid = jnp.arange(length)[None, :] < count[:, None]
    if theta:
        at = before[:, None] + jnp.arange(length)[None, :]
        q, k = _rope(q, at, theta), _rope(k, at, theta)
    keys = jnp.concatenate([past_k, k], axis=1)
    values = jnp.concatenate([past_v, v], axis=1)
    key_pos = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(past)[None, :], (n, past)),
        before[:, None] + jnp.arange(length)[None, :]], axis=1)
    key_ok = jnp.concatenate([jnp.arange(past)[None, :] < before[:, None], valid], axis=1)
    query_pos = before[:, None] + jnp.arange(length)[None, :]
    gap = query_pos[:, :, None] - key_pos[:, None, :]
    allowed = key_ok[:, None, :] & (gap >= 0) & (gap < memory_len)
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, keys,
                        preferred_element_type=jnp.float32) / (head_dim ** 0.5)
    scores = jnp.where(allowed[:, None, None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("ngrqk,nkgd->nqgrd", weights, values)
    return out.reshape(n, length, -1), k.reshape(n, length, -1)


def _operands(seed, n, length, past, heads, kv_heads):
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    draw = lambda key, *shape: jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)  # noqa: E731
    return (draw(keys[0], n, length, heads * D), draw(keys[1], n, length, kv_heads * D),
            draw(keys[2], n, length, kv_heads * D), draw(keys[3], n, past, kv_heads * D),
            draw(keys[4], n, past, kv_heads * D)), (
        draw(keys[5], n, length, heads * D), draw(keys[6], n, length, kv_heads * D))


def _far(a, b):
    """Largest difference of two arrays over the larger's scale (0 for empty ones)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max(initial=0.0) / max(np.abs(b).max(initial=0.0), 1.0))


# rows: no valid step and nothing before; the part full, the past full
# (``before`` at ``past``); a short prefix behind a short past (``before``
# under ``past``); all but the last step.  memory_len 12 is shorter than the
# 16 + 8 keys in reach, so the oldest keys fall out of the later queries' sight
LENGTH, PAST, MEMORY = 16, 8, 12


@pytest.mark.parametrize("past", [0, PAST], ids=["no_past", "past8"])
@pytest.mark.parametrize("theta", [0.0, 1e4], ids=["plain", "rope"])
@pytest.mark.parametrize("group", [1, 4], ids=["mha", "gqa4"])
def test_kernel_matches_the_einsum_lines_and_their_gradients(group, theta, past):
    kv_heads = 2
    static = (group, D, MEMORY, theta)
    operands, (w_out, w_keys) = _operands(group + past, 4, LENGTH, past, kv_heads * group, kv_heads)
    before = jnp.asarray([0, past, min(past, 3), min(past, 5)], jnp.int32)
    count = jnp.asarray([0, LENGTH, 5, LENGTH - 1], jnp.int32)

    def loss(core):
        def of(*operands):
            out, keys = core(*operands, before, count, static)
            return ((out.astype(jnp.float32) * w_out).sum()
                    + (keys.astype(jnp.float32) * w_keys).sum())
        return of

    got = attention_core.attention_core(*operands, before, count, static)
    want = einsum_lines(*operands, before, count, static)
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in got)
    # the same float32 arithmetic on the same bf16 operands: a last bit at most
    assert _far(got[0], want[0]) < 1e-2 and _far(got[1], want[1]) < 1e-2
    # the row with no valid key: the uniform mix of every key's values
    values = jnp.concatenate([operands[4], operands[2]], axis=1)[0].astype(jnp.float32)
    uniform = jnp.tile(values.reshape(-1, kv_heads, 1, D).mean(axis=0), (1, group, 1)).reshape(-1)
    assert _far(got[0][0], jnp.broadcast_to(uniform, got[0][0].shape)) < 2e-2
    grads = jax.grad(loss(attention_core.attention_core), argnums=(0, 1, 2, 3, 4))(*operands)
    wants = jax.grad(loss(einsum_lines), argnums=(0, 1, 2, 3, 4))(*operands)
    for name, a, b in zip(("q", "k", "v", "past_k", "past_v"), grads, wants):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        # bf16 cotangents rounded at different places (the kernel keeps the
        # probabilities' cotangent in float32)
        assert _far(a, b) < 2e-2, (name, _far(a, b))
    if past:   # the past's first ``before`` keys alone are seen
        assert float(jnp.abs(grads[3][0].astype(jnp.float32)).max()) == 0.0
        assert float(jnp.abs(grads[3][2, 3:].astype(jnp.float32)).max()) == 0.0
        assert float(jnp.abs(grads[3][2, :3].astype(jnp.float32)).max()) > 0.0


def test_a_short_memory_hides_old_keys_from_late_queries():
    """With ``memory_len`` 4 a query sees itself and three keys back: moving
    a key further back than that changes no output of the kernel's."""
    static = (1, D, 4, 1e4)
    operands, _ = _operands(7, 2, LENGTH, PAST, 1, 1)
    before, count = jnp.asarray([PAST, 2], jnp.int32), jnp.asarray([LENGTH, LENGTH], jnp.int32)
    out, _ = attention_core.attention_core(*operands, before, count, static)
    q, k, v, past_k, past_v = operands
    moved = attention_core.attention_core(
        q, k.at[:, 0].add(3.0), v, past_k + 1.0, past_v, before, count, static)[0]
    # query i sees new keys i-3..i: key 0 until query 3, the past until query 2
    assert _far(moved[:, 4:], out[:, 4:]) == 0.0 and _far(moved[:, :3], out[:, :3]) > 0.0


def _primitives(jaxpr):
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in sub_jaxprs(eqn):
            found |= _primitives(sub)
    return found


@pytest.mark.parametrize("dtype,head_dim,length,past,path,why", [
    (jnp.bfloat16, 128, 64, 8, "kernel", "bfloat16 heads of 128, 72 keys a row"),
    (jnp.bfloat16, 128, 72, 0, "kernel", "72 keys a row"),
    (jnp.bfloat16, 128, 8, 0, "einsum", "8 queries a row, under 64"),
    (jnp.float32, 128, 64, 8, "einsum", "float32"),
    (jnp.bfloat16, 64, 64, 8, "einsum", "head_dim 64"),
    (jnp.bfloat16, 128, 68, 8, "einsum", "whole tiles of 8"),
    (jnp.bfloat16, 128, 4096, 8, "einsum", "VMEM"),
], ids=["bf16_d128", "bf16_d128_no_past", "burn_in", "float32", "d64", "ragged_rows", "too_long"])
def test_the_path_follows_dtype_and_shape_alone(dtype, head_dim, length, past, path, why):
    """``fits`` decides, and keeps what it decided and why; the module's
    window mode holds the kernel exactly where it says so (the long case is
    asked of ``fits`` only)."""
    heads, kv_heads = 4, 2
    attention_core.PATHS.clear()
    assert attention_core.fits(dtype, length, past, heads, kv_heads, head_dim) == (path == "kernel")
    (record,) = attention_core.PATHS.values()
    assert record["path"] == path and why in record["why"], record
    assert (record["queries"], record["past"], record["head_dim"]) == (length, past, head_dim)
    if length > 128:
        return
    module = GroupedQueryAttention(32, heads, kv_heads, head_dim, 200, 1e4)
    h = jnp.zeros((2, length, 32), dtype)
    state = {"k": jnp.zeros((2, past, kv_heads, head_dim), dtype),
             "v": jnp.zeros((2, past, kv_heads, head_dim), dtype), "n": jnp.zeros((2,), jnp.int32)}
    valid = jnp.ones((2, length), bool)
    params = jax.tree.map(lambda x: x.astype(dtype), module.init(jax.random.PRNGKey(0), h, state, valid))
    found = _primitives(jax.make_jaxpr(lambda p: module.apply(p, h, state, valid))(params).jaxpr)
    assert ("pallas_call" in found) == (path == "kernel"), found
    # step mode (acting) never takes the kernel
    ring = {"k": jnp.zeros((2, 200, kv_heads, head_dim)), "v": jnp.zeros((2, 200, kv_heads, head_dim)),
            "pos": jnp.zeros((2,))}
    assert "pallas_call" not in _primitives(
        jax.make_jaxpr(lambda p: module.apply(p, h[:, 0], ring))(params).jaxpr)


def test_the_module_keeps_the_same_state_and_gradients_on_either_path(monkeypatch):
    """``GroupedQueryAttention`` in window mode with bf16 heads of 128: what
    it returns, the state it hands on (the past's keys, then the new ones
    rotated) and the gradient of its parameters, input and past, through
    the kernel and, with ``fits`` answering no, through the einsum lines."""
    LENGTH = attention_core.ROWS_MIN          # the shortest part the kernel takes
    module = GroupedQueryAttention(64, 4, 2, D, MEMORY, 1e4)
    n, rng = 3, jax.random.split(jax.random.PRNGKey(1), 4)
    h = jax.random.normal(rng[0], (n, LENGTH, 64), jnp.bfloat16)
    past = {"k": jax.random.normal(rng[1], (n, PAST, 2, D), jnp.bfloat16),
            "v": jax.random.normal(rng[2], (n, PAST, 2, D), jnp.bfloat16)}
    before = jnp.asarray([0, PAST, 5], jnp.int32)
    valid = jnp.arange(LENGTH)[None, :] < jnp.asarray([0, LENGTH, 7])[:, None]
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          module.init(rng[3], h, dict(past, n=before), valid))

    def run(params, h, past):
        out, new = module.apply(params, h, dict(past, n=before), valid)
        return ((out.astype(jnp.float32) ** 2).sum()
                + 0.1 * (new["k"].astype(jnp.float32) ** 2).sum()), (out, new)

    both = []
    for kernel in (True, False):
        if not kernel:
            monkeypatch.setattr(attention_core, "fits", lambda *a: False)
        both.append(jax.value_and_grad(run, argnums=(0, 1, 2), has_aux=True)(params, h, past))
    ((_, (out, new)), grads), ((_, (out_e, new_e)), grads_e) = both
    assert new["k"].shape == (n, PAST + LENGTH, 2, D) and list(new["n"]) == [0, PAST + LENGTH, 12]
    assert list(new["n"]) == list(new_e["n"])
    assert _far(out, out_e) < 1e-2 and _far(new["k"], new_e["k"]) < 1e-2
    assert _far(new["v"], new_e["v"]) == 0.0
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_e)):
        assert _far(a, b) < 2e-2


def test_the_train_step_runs_its_forward_part_through_the_kernel_and_says_so(tmp_path, monkeypatch):
    """A looped ``*-`` trunk with bf16 heads of 128 on Geister windows of 8
    burn-in and 64 forward steps, of which a player observes 32 at most
    (``ROWS_MIN`` is lowered to that here): the scanned, checkpointed step
    holds the kernel in its forward part (the 8 burn-in steps are under
    ``ROWS_MIN`` and keep the einsum lines, whose keys the kernel then reads
    as its past), its loss is finite, and the context writes one
    ``model.attention_path`` event a part once a tracer is on: the first
    update made under it, as the benchmark's traced runs turn theirs on
    after the warm-up."""
    from benchmark import traffic
    from handyrl_tpu.utils import trace

    cfg = normalize_args({
        "env_args": {"env": "Geister", "net": "hybrid", "net_args": dict(
            pattern="*-", loops=2, sandwich=True, d_model=32, n_heads=2, n_kv_heads=1,
            head_dim=D, rope_theta=1e4, mlp_width=64, memory_len=200)},
        "train_args": {"batch_size": 2, "burn_in_steps": 8, "forward_steps": 64,
                       "observation": True, "seq_forward": True, "remat": "block",
                       "compute_dtype": "bfloat16", "seed": 3}})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(3)
    np.random.seed(3)
    env = make_env(args["env"])
    module = env.net()
    params = traffic.seeded_params(module, env, 3)
    batch = traffic.random_play_batches(env, module, args, 1, 4)[0]
    attention_core.PATHS.clear()
    monkeypatch.setattr(attention_core, "ROWS_MIN", 32)
    ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    state = ctx.init_state(params)
    device_batch = ctx.put_batch(batch)
    state, metrics = ctx.train_step(state, device_batch, 1e-4)      # traces; no tracer yet
    paths = {key[1:3]: record["path"] for key, record in attention_core.PATHS.items()}
    assert paths == {(8, 0): "einsum", (32, 8): "kernel"}, attention_core.PATHS
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        for _ in range(2):
            state, metrics = ctx.train_step(state, device_batch, 1e-4)
    finally:
        trace.shutdown()
    metrics = jax.device_get(metrics)
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    events = [r["attrs"] for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
              if r["name"] == "model.attention_path"]
    assert sorted((e["queries"], e["past"], e["path"]) for e in events) == [
        (8, 0, "einsum"), (32, 8, "kernel")]
    assert all(e["dtype"] == "bfloat16" and e["head_dim"] == D for e in events)
    assert sorted(e["why"] for e in events) == [
        "8 queries a row, under 32", "bfloat16 heads of 128, 40 keys a row in VMEM"]
