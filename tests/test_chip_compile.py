"""The Pallas kernels of the main paths at the shapes chip_smoke.py and the
benchmark's cells pin, compiled for a described (not attached) TPU v5e by the
chip's own compiler: both attention kernels, the routed experts' grouped
products at ``nemotron_twotower_train_t192``'s and ``zaya1_8b``'s widths, a
window part's attention core at the ``HybridNet`` cells' widths, and the
acting rows' SSM step.  The whole programs are beside it since PR 67 (so that
no one xdist worker compiles them all): the d1536 step's ring and
``kanana2_train_t192``'s step in tests/test_chip_compile_steps.py,
``trinity_mini_train_t192``'s step and the actor cell's rollout in
tests/test_chip_compile_cells.py.

Interpret-mode tests cannot see what the TPU compiler refuses (a slice
not aligned to the tiling, too much VMEM); these compiles can, at about
two seconds each and no chip time.  Nothing executes, so this checks that
the kernel is IN the program (``tpu_custom_call``) and that its forward
and its custom-VJP backward compile — not results, not times.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from described_v5e import _no_compile_cache, v5e, v5e_2x2  # noqa: F401  (fixtures)
from handyrl_tpu.ops.flash_attention import flash_attention, masked_flash_attention

_NET = chip_smoke.TRANSFORMER_TPU_NET_ARGS
_STEP = chip_smoke.TRANSFORMER_TPU_OVERRIDES
_LONG = chip_smoke.TRANSFORMER_LONG_TPU
_HD = (_NET["n_heads"], _NET["d_model"] // _NET["n_heads"])

WINDOW = _NET["memory_len"]  # of every pinned transformer shape

# (B, T, H, D): the smoke's and the xfmr_train_t64 cell's step (B64 x 2
# players, T64) and the two long rows of TRANSFORMER_LONG_TPU, all d1536 /
# 16 heads -> D96, which pads to the 128-lane tile inside the kernel
PINNED = [(2 * _STEP["batch_size"], _STEP["burn_in_steps"] + _STEP["forward_steps"]) + _HD] + [
    (_LONG["batch_by_t"][t], t) + _HD for t in _LONG["sweep_t"][1:]
]
# T not a multiple of the 128 tile: only the masked kernel pads T
UNALIGNED = (8, 200, 16, 96)


def _kernel_fn(kernel, shape, sharding):
    """(fn(q, k, v), avals) calling ``kernel`` compiled (interpret=False —
    the auto-pick would see this process's CPU backend)."""
    B, T, H, D = shape
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)
    if kernel == "flash":
        return (lambda q, k, v: flash_attention(q, k, v, True, 128, 128, False)), qkv
    key_mask = jnp.ones((B, T), jnp.float32)
    slopes = 2.0 ** (-jnp.arange(1, H + 1, dtype=jnp.float32))
    return (
        lambda q, k, v: masked_flash_attention(
            q, k, v, key_mask, slopes, WINDOW, 128, 128, False
        )
    ), qkv


@pytest.mark.parametrize("mode", ["forward", "grad"])
@pytest.mark.parametrize(
    "kernel,shape",
    [(k, s) for k in ("flash", "masked") for s in PINNED] + [("masked", UNALIGNED)],
    ids=lambda v: v if isinstance(v, str) else "B%d-T%d-H%d-D%d" % v,
)
def test_kernel_compiles_for_v5e(v5e, kernel, shape, mode):
    fn, qkv = _kernel_fn(kernel, shape, v5e)
    if mode == "grad":
        fwd = fn
        fn = jax.grad(
            lambda q, k, v: (fwd(q, k, v).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2),
        )
    compiled = jax.jit(fn).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        "the Pallas kernel is not in the compiled program"
    )


# -- the routed experts' grouped products (ops/grouped_product.py) ---------

# nemotron_twotower_30b_a3b as benchmark/configs/ has it: hidden 2,688, expert
# width 1,856 = 14.5 x 128, 8 of 128 experts held, top-6
_EXPERTS = dict(d=2688, width=1856, held=8, experts=128, top_k=6)


def _no_pass_over_the_weights_sums(lowered, compiled_text, *shapes):
    """The backward loop of a routed part carries the sums of the weights'
    gradients, each of a weight's ``shape``: the kernel that computes a
    gradient adds it to its sum where the sum lies and writes the first
    pass's without reading, so no ``add`` of two weight-shaped operands is in
    the program as lowered (the loop's body is where one was, PERF.md, PR
    50), and as compiled no ``add`` or zero ``broadcast`` gives a
    weight-shaped result: the carry starts as an allocation."""
    for shape in shapes:
        tensor = "tensor<%sxbf16>" % "x".join(map(str, shape))
        adds = [line.strip() for line in lowered.as_text().splitlines()
                if "stablehlo.add" in line and tensor in line]
        assert not adds, adds[:2]
        result = re.compile(r"= bf16\[%s\]\S* (add|broadcast)\(" % ",".join(map(str, shape)))
        passes = [line.strip()[:160] for line in compiled_text.splitlines() if result.search(line)]
        assert not passes, passes[:2]
        assert re.search(r"= bf16\[%s\]\S* custom-call\(\), custom_call_target=\"AllocateBuffer\""
                         % ",".join(map(str, shape)), compiled_text), shape


@pytest.mark.parametrize("tokens", [6144, 512, 2], ids=["forward_part", "burn_in_part", "acting"])
def test_routed_experts_compile_for_v5e_through_the_grouped_kernel(v5e, monkeypatch, tokens):
    """``held_mix`` with bf16 operands and its gradient at the published
    widths: the cell's two window parts (64 rows x 96 packed steps, x 8
    burn-in steps) and the handful of tokens step mode acts on.  The width is
    no multiple of 128 and a weight tile is 10 MB, double-buffered: what the
    interpreter cannot refuse.  Every product is the kernel (two forward,
    two rows' cotangents, two weight sums), each of its calls carries the
    ``experts`` scope the benchmark times it under, and no block's weights
    are copied out (no (blocks, d, width) operand, no exact one-hot pick)."""
    from benchmark import trace_reduce
    from handyrl_tpu.ops.routed_experts import EXPERTS_SCOPE, block_rows, held_mix, row_buffer

    d, width, held, experts, k = (_EXPERTS[key] for key in ("d", "width", "held", "experts", "top_k"))
    # the auto-pick would see this process's CPU backend and hand over the interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731

    def loss(h, gates, w1, w2, chosen, valid):
        out, _ = held_mix(h, chosen, gates, valid, w1, w2, 0, experts)
        return (out.astype(jnp.float32) ** 2).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        aval((tokens, d), jnp.bfloat16), aval((tokens, k), jnp.float32),
        aval((held, d, width), jnp.bfloat16), aval((held, width, d), jnp.bfloat16),
        aval((tokens, k), jnp.int32), aval((tokens,), jnp.bool_))
    text = lowered.compile().as_text()
    _no_pass_over_the_weights_sums(lowered, text, (held, d, width), (held, width, d))
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    # the forward pass's two products; under the hand-written backward those
    # two again, two rows' cotangents and two weight sums
    assert len(calls) == 2 + 6, len(calls)
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert all(trace_reduce.scopes_of(n, [EXPERTS_SCOPE]) == [EXPERTS_SCOPE] for n in names), names
    # the window's parts in the MXU's tile; 12 pairs over 128 experts in the least a block can be
    block = block_rows(tokens, k, experts, jnp.bfloat16)
    blocks = row_buffer(tokens, k, held, experts, block)[0]
    assert (blocks, block) == {6144: (53, 128), 512: (12, 128), 2: (9, 16)}[tokens]
    assert "[%d,%d]" % (blocks * block, d) in text
    for copied in ("[%d,%d,%d]" % (blocks, d, width), "[%d,%d,%d]" % (blocks, width, d)):
        assert copied not in text
    assert "precision_config" not in text or "HIGHEST" not in text


def test_acting_rows_routed_experts_compile_for_v5e_in_blocks_of_sixteen(v5e, monkeypatch):
    """``held_mix`` as ``granite_actor_b32``'s rollout step calls it (32
    acting rows x 4,096 in bfloat16, top-10 of 72 with 36 held, ``gated``
    experts of 768): ~4.5 rows a held expert, so the row buffer lies in
    blocks of 16, 56 of them = 896 slots, where blocks of 128 made it 39 x
    128 = 4,992 (PERF.md, PR 46).  Both products are the kernel, under the
    ``experts`` scope, and nothing in the program has 4,992 rows."""
    from benchmark import trace_reduce
    from handyrl_tpu.ops.routed_experts import EXPERTS_SCOPE, block_rows, held_mix, row_buffer

    tokens, d, width, held, experts, k = 32, 4096, 768, 36, 72, 10
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731
    text = jax.jit(lambda h, gates, w1, w2, chosen, valid: held_mix(
        h, chosen, gates, valid, w1, w2, 0, experts, True)).lower(
        aval((tokens, d), jnp.bfloat16), aval((tokens, k), jnp.float32),
        aval((held, d, 2 * width), jnp.bfloat16), aval((held, width, d), jnp.bfloat16),
        aval((tokens, k), jnp.int32), aval((tokens,), jnp.bool_)).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2, len(calls)
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert all(trace_reduce.scopes_of(n, [EXPERTS_SCOPE]) == [EXPERTS_SCOPE] for n in names), names
    block = block_rows(tokens, k, experts, jnp.bfloat16)
    blocks, passes = row_buffer(tokens, k, held, experts, block)
    assert (block, blocks, passes) == (16, 56, 1)      # one pass covers every pair on held experts
    # the forward half is the rows' kernel alone: no weight sum, whose carried sum is an
    # operand aliased to the output (the backward loop's, PR 50)
    assert "_rows_times" in text and "_weight_sums" not in text
    assert not any("output_to_operand_aliasing" in line for line in calls)
    assert "f32[896,%d]" % (2 * width) in text and "f32[896,%d]" % d in text
    assert "[4992," not in text and "[%d,%d,%d]" % (blocks, d, 2 * width) not in text


@pytest.mark.parametrize("tokens", [6144, 512], ids=["forward_part", "burn_in_part"])
def test_gated_top1_experts_and_their_gradient_compile_for_v5e_at_zaya1s_widths(v5e, monkeypatch,
                                                                                 tokens):
    """``held_mix`` with ``gated`` bf16 operands and its gradient at
    ``zaya1_8b``'s widths (a 2,048-wide stream, 8 of 16 experts of width 2,048
    held, one choice a token): the fused gate-and-up matrix is (2048, 4096),
    whose weight sum as one tile (a float32 sum and a double-buffered output)
    is 64 MB and over the kernel's scope, so ``_weight_sums`` takes it in two
    column tiles: what the interpreter cannot refuse and this compile did (PR
    48).  Six kernel calls under the backward beside the forward's two, and
    the forward part's 56 blocks are its worst case: one pass."""
    from handyrl_tpu.ops.routed_experts import block_rows, held_mix, row_buffer

    d, width, held, experts, k = 2048, 2048, 8, 16, 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731

    def loss(h, gates, w1, w2, chosen, valid):
        out, _ = held_mix(h, chosen, gates, valid, w1, w2, 0, experts, True)
        return (out.astype(jnp.float32) ** 2).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        aval((tokens, d), jnp.bfloat16), aval((tokens, k), jnp.float32),
        aval((held, d, 2 * width), jnp.bfloat16), aval((held, width, d), jnp.bfloat16),
        aval((tokens, k), jnp.int32), aval((tokens,), jnp.bool_))
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2 + 6, len(calls)
    _no_pass_over_the_weights_sums(lowered, text, (held, d, 2 * width), (held, width, d))
    block = block_rows(tokens, k, experts, jnp.bfloat16)
    assert (block,) + row_buffer(tokens, k, held, experts, block) == {
        6144: (128, 56, 1), 512: (128, 12, 1)}[tokens]


@pytest.mark.parametrize("tokens", [6144, 512], ids=["forward_part", "burn_in_part"])
def test_a_scan_over_periods_of_stacked_experts_compiles_for_v5e_with_no_copy_of_a_period(
        v5e, monkeypatch, tokens):
    """``held_mix`` under a ``lax.scan`` over three periods at ``zaya1_8b``'s
    widths (the test above's), handed the periods' stacked weights, the period
    and the sinks, and its gradient: the kernels compile inside their VMEM
    scope with the period as one more prefetched coordinate, and outside them
    nothing in the compiled program is rooted at a stacked or a per-period
    weight shape that copies, slices, adds or zero-fills one: no
    ``dynamic-slice`` (the scan's way out of a stack, which XLA cannot fuse
    into a custom call), no ``dynamic-update-slice`` (its way back in), no
    ``copy`` at a loop's boundary, no ``add`` of a closed-over stack's
    cotangent, no ``broadcast`` of zeros; the stacked gradients start as
    allocations (PERF.md, PR 51).  Each kernel is in the program once a call
    site: two forward, and under the backward scan those two again, two rows'
    cotangents and two weight sums whose stacked sum is aliased to the
    output."""
    from handyrl_tpu.ops.routed_experts import held_mix, open_sinks

    d, width, held, experts, k, periods = 2048, 2048, 8, 16, 1, 3
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731

    def loss(h, gates, w1, w2, chosen, valid):
        read = jax.lax.stop_gradient((w1, w2))

        def one_period(carry, t):
            h, sinks = carry
            out, counts = held_mix(h, chosen, gates, valid, *read, 0, experts, True, t, sinks)
            return (h + out, counts["sinks"]), None

        (h, sinks), _ = jax.lax.scan(one_period, (h, (w1, w2)), jnp.arange(periods))
        return (open_sinks(h, sinks).astype(jnp.float32) ** 2).sum()

    shapes = ((periods, held, d, 2 * width), (periods, held, width, d))
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        aval((tokens, d), jnp.bfloat16), aval((tokens, k), jnp.float32),
        aval(shapes[0], jnp.bfloat16), aval(shapes[1], jnp.bfloat16),
        aval((tokens, k), jnp.int32), aval((tokens,), jnp.bool_))
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2 + 6, len(calls)
    sums = [line for line in calls if "output_to_operand_aliasing" in line]
    assert len(sums) == 2 and all("_weight_sums" in line for line in sums), len(sums)
    for shape in shapes + tuple(shape[1:] for shape in shapes):
        dims = ",".join(map(str, shape))
        # a slice of a stack keeps a leading 1 until a bitcast drops it
        rooted = re.compile(r"= bf16\[(1,)?%s\]\S* (dynamic-slice|dynamic-update-slice|copy|"
                            r"copy-start|add|broadcast)\(" % dims)
        found = [line.strip()[:160] for line in text.splitlines() if rooted.search(line)]
        assert not found, found[:3]
    _no_pass_over_the_weights_sums(lowered, text, *shapes)


# sha256 of the tiny ``nemotron_*`` cell's train step as lowered on the CPU
# (``benchmark/tests/tiny_hybrid/``: ``MEM*E``, no period to scan), in
# float32 (the plain block products) and in bfloat16 (the grouped kernel in
# the interpreter).  A change that means to alter that program replaces
# these; one that only adds a path for another net must not.  They stood from
# the commit before PR 51 to PR 60, which meant to: the kernels are handed
# ``live``, ``down`` is weighed by the gate through ``_weigh``, and the step
# counts ``slots_run`` (float32: the last two).
_TINY_ROUTED_STEP = {
    "float32": "e9ffdf3376824601e36a0b1c44b58da1533a1da08ec2d7844a794b6ef5f57322",
    "bfloat16": "da0fd3df5ec1223fc9aadf23493b973195d876edf9f47d2187fe90e27fe28996",
}


@pytest.mark.parametrize("dtype", sorted(_TINY_ROUTED_STEP))
def test_a_routed_step_without_periods_lowers_to_the_text_it_had(dtype):
    """The grouped kernels take a period only where they are handed one: the
    tiny ``nemotron_*`` cell's step, which unrolls its stack and calls them
    without, lowers to the text it had before they could (PERF.md, PR 51: a
    Pallas program's cache key holds its callers, so the routed cells' first
    runs are cold after any edit there; the program they compile is not to
    change with it)."""
    import hashlib
    import json
    import random

    import numpy as np

    from benchmark import traffic
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh

    tiny = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "tests", "tiny_hybrid")
    with open(os.path.join(tiny, "workloads", "tiny_hybrid_train.json")) as f:
        cell = json.load(f)
    with open(os.path.join(tiny, "configs", "tiny_hybrid.json")) as f:
        config = json.load(f)
    cfg = normalize_args({"env_args": dict(config["env_args"]),
                          "train_args": dict(cell["train_args"], compute_dtype=dtype, seed=1)})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(1)
    np.random.seed(1)
    env = make_env(args["env"])
    module = env.net()
    ctx = TrainContext(module, args, make_mesh({"dp": 1}, devices=jax.devices()[:1]))
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    params = jax.eval_shape(
        lambda key: module.init(key, obs, module.initial_state((1,)))["params"],
        jax.random.PRNGKey(0))
    state = {"params": params, "opt_state": jax.eval_shape(ctx.tx.init, params),
             "steps": jax.ShapeDtypeStruct((), jnp.int32)}
    small = traffic.random_play_batches(env, module, dict(args, batch_size=2), 1, 4)[0]
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (int(args["batch_size"]),) + np.shape(x)[1:], np.asarray(x).dtype), small)
    text = jax.jit(ctx._step_fn, donate_argnums=(0,)).lower(
        state, batch, jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    assert "stablehlo.while" in text and "4x32x32x" in text      # the pass loops, the held experts
    assert hashlib.sha256(text.encode()).hexdigest() == _TINY_ROUTED_STEP[dtype]


# -- a window part's attention core (ops/attention_core.py) ------------------

# (d_model, query heads, KV heads) of the two HybridNet cells, heads of 128:
# ouro_2_6b and nemotron_twotower_30b_a3b as benchmark/configs/ has them
_ATTENTION = {"ouro": (2048, 16, 16, 1e6), "nemotron": (2688, 32, 2, 0.0)}


@pytest.mark.parametrize("part,length,past", [("packed", 96, 8), ("unpacked", 184, 8)])
def test_compressed_convolutional_attention_compiles_for_v5e_round_the_kernel(v5e, monkeypatch, part,
                                                                              length, past):
    """``CompressedConvAttention``'s window mode and its gradient at
    ``zaya1_8b``'s widths (8 query and 2 key/value heads of 128 on a
    2,048-wide stream, half of each head rotated, two convolutions over
    two steps) and the cell's forward parts: the attention core is
    ``ops/attention_core.py``'s kernel, handed operands the mixer normed and
    rotated itself (forward and backward call under ``gqa``), and the
    grouped convolution, the norms and the rotation compile beside it."""
    from benchmark import trace_reduce
    from handyrl_tpu.models.hybrid import CCA_SCOPE, GQA_SCOPE, CompressedConvAttention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    module = CompressedConvAttention(2048, 8, 2, 128, 200, 5e6, 64, 2, 2)
    aval = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731
    h, valid = aval((64, length, 2048)), aval((64, length), jnp.bool_)
    state = {"k": aval((64, past, 2, 128)), "v": aval((64, past, 2, 128)),
             "n": aval((64,), jnp.int32), "tail": aval((64, 2, 1280), jnp.float32),
             "prev_v": aval((64, 128), jnp.float32)}
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    params = jax.tree.map(lambda x: aval(x.shape), jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros(h.shape, h.dtype), zeros, jnp.ones(valid.shape, bool))))

    def loss(params, h, state, valid):
        out, new = module.apply(params, h, state, valid)
        return (out.astype(jnp.float32) ** 2).sum() + sum(
            (x.astype(jnp.float32) ** 2).sum() for x in jax.tree.leaves(new))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, h, state, valid).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2, len(calls)
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert all(trace_reduce.scopes_of(n, [GQA_SCOPE]) == [GQA_SCOPE] for n in names), names
    mixed = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if trace_reduce.scopes_of(n, [CCA_SCOPE]) == [CCA_SCOPE]]
    assert any("dot_general" in n for n in mixed) and any("transpose(jvp" in n for n in mixed)


@pytest.mark.parametrize("part,length,past", [("packed", 96, 8), ("unpacked", 184, 8)])
@pytest.mark.parametrize("cell", sorted(_ATTENTION))
def test_attention_core_compiles_for_v5e_where_the_projections_wrote(v5e, monkeypatch, cell, part,
                                                                      length, past):
    """``GroupedQueryAttention``'s window mode and its gradient at the two
    cells' widths and forward parts (64 rows of 96 packed steps behind 8
    burn-in steps, as the step runs them, and the 184 steps of the unpacked
    window ``judge_forward`` runs; the 8 burn-in steps themselves are under
    ``ROWS_MIN`` and keep the einsum lines): the forward and the backward
    kernel are in the program, each under the ``gqa`` scope the benchmark times them
    under, and no whole-array ``copy`` of a (64, steps, ...) operand stands
    before or behind them: the kernel reads q, k and v where the
    projections wrote them and writes where ``o`` reads."""
    from benchmark import trace_reduce
    from handyrl_tpu.models.hybrid import GQA_SCOPE, GroupedQueryAttention

    d_model, heads, kv_heads, theta = _ATTENTION[cell]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    module = GroupedQueryAttention(d_model, heads, kv_heads, 128, 200, theta)
    aval = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731
    h, valid = aval((64, length, d_model)), aval((64, length), jnp.bool_)
    state = {"k": aval((64, past, kv_heads, 128)), "v": aval((64, past, kv_heads, 128)),
             "n": aval((64,), jnp.int32)}
    params = jax.tree.map(
        lambda x: aval(x.shape),
        jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), jnp.zeros(h.shape, h.dtype), {
            "k": jnp.zeros(state["k"].shape, h.dtype), "v": jnp.zeros(state["v"].shape, h.dtype),
            "n": jnp.zeros((64,), jnp.int32)}, jnp.ones(valid.shape, bool))))

    def loss(params, h, past_k, past_v, n, valid):
        out, new = module.apply(params, h, {"k": past_k, "v": past_v, "n": n}, valid)
        return ((out.astype(jnp.float32) ** 2).sum() + (new["k"].astype(jnp.float32) ** 2).sum())

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        params, h, state["k"], state["v"], state["n"], valid).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 2, len(calls)
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert all(trace_reduce.scopes_of(n, [GQA_SCOPE]) == [GQA_SCOPE] for n in names), names
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= bf16\[64,(%d|%d)," % (length, length + past), line) and " copy(" in line]
    assert not copies, copies


# -- the actor cell's rollout program (granite_actor_b32) ---------------------

@pytest.mark.parametrize("players", [2, 4])
def test_ssd_step_rows_compiles_for_v5e_and_writes_the_buffer_it_read(v5e, players):
    """``ops/ssd.py``'s in-place step at the published Mamba-2 widths (128
    heads of 64 x 128, float32) for 32 lanes: Mosaic takes the kernel (a
    4.2 MB row read and written, both double-buffered, under the 64 MB scope it
    asks for), the donated state is the output's buffer and the program
    holds no second copy of it."""
    from handyrl_tpu.ops import ssd

    n, h, p, g, s = 32, 128, 64, 1, 128
    aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731
    width = h * p + 2 * g * s       # the conv tail beside it: 3 rows of 8,448
    fn = jax.jit(
        lambda x, dt, A, B, C, state, player, fresh, leaf, rows: ssd.ssd_step_rows(
            x, dt, A, B, C, state, player, fresh, (leaf, rows), interpret=False),
        donate_argnums=(5, 8))
    compiled = fn.lower(
        aval((n, h, p), jnp.bfloat16), aval((n, h), jnp.float32), aval((h,), jnp.float32),
        aval((n, g, s), jnp.bfloat16), aval((n, g, s), jnp.bfloat16),
        aval((n, players, h, p, s), jnp.float32), aval((n,), jnp.int32), aval((n,), jnp.bool_),
        aval((n, players, 3, width), jnp.float32), aval((n, 3, width), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory, state = compiled.memory_analysis(), 4 * n * players * (h * p * s + 3 * width)
    assert memory.alias_size_in_bytes >= state and memory.temp_size_in_bytes < state // 8


# -- a bfloat16 window part's Mamba-2 core (ops/ssd.py ssd_window) -------------

@pytest.mark.parametrize("part,length,handed_on", [
    ("burn_in", 8, True), ("packed", 96, False), ("unpacked", 184, True), ("longest", 312, True)])
def test_the_mamba_window_core_compiles_for_v5e_under_the_scope_that_times_it(v5e, monkeypatch,
                                                                              part, length,
                                                                              handed_on):
    """``Mamba2Mixer``'s window mode and its gradient at
    ``nemotron_twotower_30b_a3b``'s widths (64 heads of 64 in 8 groups, state
    128, on a 2,688-wide stream) and the cell's parts: 64 rows of 96 packed
    steps and the 184 of the unpacked window ``judge_forward`` runs go
    through ``ops/ssd.py``'s kernels, each a Mosaic call under the ``ssd``
    scope the benchmark times, and no (rows, heads, steps, steps) decay or
    score tile is left in the program.  A caller that drops the part's last
    state, as the train step does, runs the forward and the backward kernel
    and no other; one that uses it (``handed_on``) also the state's own.
    The 8 burn-in steps keep the lines and hold no kernel; 312 steps are
    the longest part ``VMEM_WINDOW`` lets through, and Mosaic takes them."""
    from benchmark import trace_reduce
    from handyrl_tpu.models.hybrid import Mamba2Mixer
    from handyrl_tpu.ops import ssd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    module = Mamba2Mixer(2688, 64, 64, 8, 128, 4, 128, 1e-5, 1e-3, 0.1, 1e-4)
    aval = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)  # noqa: E731
    h, valid = aval((64, length, 2688)), aval((64, length), jnp.bool_)
    state = {"ssm": aval((64, 64, 64, 128), jnp.float32), "conv": aval((64, 3, 6144), jnp.float32)}
    zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), state)
    params = jax.tree.map(lambda x: aval(x.shape), jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros(h.shape, h.dtype), zeros, jnp.ones(valid.shape, bool))))

    def loss(params, h, state, valid):
        out, new = module.apply(params, h, state, valid)
        return (out.astype(jnp.float32) ** 2).sum() + (
            (new["ssm"] ** 2).sum() if handed_on else 0.0)

    ssd.WINDOW_PATHS.clear()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        params, h, state, valid).compile().as_text()
    (chosen,) = [made for key, made in ssd.WINDOW_PATHS.items() if key[0] == "bfloat16"]
    ssd.WINDOW_PATHS.clear()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    if part == "burn_in":
        assert chosen["path"] == "lines" and not calls
        return
    assert chosen["path"] == "kernel" and len(calls) == 2 + handed_on, (chosen, len(calls))
    names = [re.search(r'op_name="([^"]*)"', line).group(1) for line in calls]
    assert all(trace_reduce.scopes_of(n, ["ssd"]) == ["ssd"] for n in names), names
    assert not re.search(r"f32\[\d+,\d+,\d+,\d+,%d,%d\]" % (length, length), text)
