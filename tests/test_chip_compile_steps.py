"""Whole train steps compiled for a described (not attached) TPU v5e by the
chip's own compiler: the d1536 step on a {dp: 4} mesh, whose compile shows
the schedule the chip will run (which collectives are in it and what runs
between a collective-permute's start and its done), on {dp: 1}, and
``kanana2_train_t192``'s step (latent attention) as its files give it, held
to the chip's memory.  Cut from tests/test_chip_compile.py (PR 67), which
keeps the kernels.
"""

import re

import jax

import chip_smoke
from described_v5e import _lowered, _no_compile_cache, v5e_2x2  # noqa: F401  (fixtures)

_NET = chip_smoke.TRANSFORMER_TPU_NET_ARGS
_STEP = chip_smoke.TRANSFORMER_TPU_OVERRIDES


# -- the d1536 train step on a described {dp: N} mesh ----------------------

def _lowered_step(topo, dp, batch_size, n_layers=2):
    """The cell's train step (xfmr_train_t64*: d1536, T64, bf16, einsum) at
    ``n_layers`` blocks, lowered for a {dp: dp} mesh of the described
    chips from shapes alone.  Returns (context, lowered)."""
    return _lowered(
        topo, dp, {"env": "Geister", "net": "transformer",
                   "net_args": dict(_NET, n_layers=n_layers)},
        dict(_STEP, batch_size=batch_size, seq_attention="einsum"))


def _entry_ops(hlo_text):
    """The entry computation's instructions, in schedule order."""
    body = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo_text, re.S | re.M).group(1)
    return [line.strip() for line in body.splitlines() if " = " in line]


def _bytes(shape_text):
    """Bytes of every array in an HLO result type, tuples included."""
    widths = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}
    total = 0
    for dtype, dims in re.findall(r"\b(bf16|f16|f32|s32|u32|pred|s8|u8)\[([\d,]*)\]", shape_text):
        count = 1
        for d in filter(None, dims.split(",")):
            count *= int(d)
        total += count * widths[dtype]
    return total


def test_dp4_step_rings_its_gradient_under_compute(v5e_2x2):
    """{dp: 4}: no all-reduce over 2 MB is left in the program; every ring
    hop's collective-permute has ops scheduled between its start and its
    done; and the hops sit INSIDE the backward pass, where the chip's trace
    measured them (PERF.md, PR 31): a section's backward pass starts only
    once every hop of the sections two or more nearer the loss is done, so
    each section's ring has the next section's backward pass to run under.
    (With nothing in the backward pass waiting for a sum, or with the wait
    folded away, the scheduler runs every hop behind the last backward op,
    and the chip's trace shows them all exposed there.)"""
    n_layers = 3
    ctx, lowered = _lowered_step(
        v5e_2x2, dp=4, batch_size=4 * _STEP["batch_size"], n_layers=n_layers
    )
    assert ctx.grad_sync["ring_leaves"] == n_layers * 6 + 2    # the blocks, enc2, policy
    ops = _entry_ops(lowered.compile().as_text())
    started, gaps, done_at, backward_from = {}, [], [], {}
    for at, op in enumerate(ops):
        name = re.match(r"(?:ROOT )?%?([\w.\-]+) = ", op).group(1)
        if " collective-permute-start(" in op:
            started[name] = at
        elif " collective-permute-done(" in op:
            source = re.search(r"collective-permute-done\([^%]*%([\w.\-]+)", op).group(1)
            gaps.append(at - started[source])
            done_at.append(at)
        elif re.search(r" all-reduce(-start)?\(", op):
            result_type = op.partition(" = ")[2].partition(" all-reduce")[0]
            assert _bytes(result_type) <= 2 << 20, op[:200]
        # the first op of each section's backward pass, by the name jax gave it
        section = re.search(
            r'op_name="[^"]*transpose\(jvp\(TransformerNet\.(\w+)\)\)/[a-z_]+(\d*)/', op
        )
        if section:
            which = section.group(1)    # heads, encode, or a block and its number
            backward_from.setdefault(which + section.group(2) * (which == "block"), at)
    # a section's hops: one buffer a row shape (a block has rows of 1536 and
    # of 6144), both ways, both rounds
    hops = {"heads": 1, "encode": 1, **{f"block{i}": 2 for i in range(n_layers)}}
    hops = {k: v * 2 * 2 * (4 - 1) for k, v in hops.items()}
    assert len(gaps) == sum(hops.values())
    assert min(gaps) >= 2, "a collective-permute's done sits right behind its start"
    backward = ["heads"] + [f"block{i}" for i in reversed(range(n_layers))] + ["encode"]
    assert sorted(backward_from, key=backward_from.get) == backward
    for k, section in enumerate(backward[2:]):
        due = sum(hops[s] for s in backward[:k + 1])
        done = sum(at < backward_from[section] for at in done_at)
        assert done >= due, (section, done, due)


def test_dp1_step_updates_in_its_own_computation_under_one_norm(v5e_2x2):
    """{dp: 1}, two blocks: the compiled step holds no ``conditional`` (the
    sentinel's verdict is a select inside each leaf's update fusion; a
    conditional's boundary fixes a layout per operand and hides its body
    from CSE), so the clip's norm and the sentinel's are one: each leaf's
    square sum is folded into the fusion that makes its gradient, and a
    dozen reduce fusions of their own are left where the parent's branch
    read every leaf a second time (70 with the ``lax.cond``, 9 without:
    PERF.md, PR 38).  Not the bytes: at two blocks ``cost_analysis`` counts
    the compiler's prefetch slices and does not fall."""
    _, lowered = _lowered_step(v5e_2x2, dp=1, batch_size=_STEP["batch_size"], n_layers=2)
    text = lowered.compile().as_text()
    assert " conditional(" not in text
    norms = re.findall(r"%?multiply_reduce_fusion[.\d]* = f32\[\][^ ]* fusion\(", text)
    assert 0 < len(norms) <= 12, len(norms)


def test_dp1_step_lowers_without_the_ring(v5e_2x2):
    """{dp: 1} (the one-chip cells) lowers to the program it always was:
    nothing of the sections' sums is in its text, all of it is in {dp: 4}'s."""
    words = ("collective_permute", "all_reduce", "manual_computation")
    ctx4, lowered4 = _lowered_step(v5e_2x2, dp=4, batch_size=8, n_layers=1)
    text4 = lowered4.as_text()
    for word in words:
        assert word in text4, word
    ctx1, lowered1 = _lowered_step(v5e_2x2, dp=1, batch_size=8, n_layers=1)
    text1 = lowered1.as_text()
    assert ctx1.grad_sync is None
    for word in words + ("shard_map", "psum"):
        assert word not in text1, word


# -- a trained cell's whole step (kanana2_train_t192) ------------------------

def test_the_latent_attention_cells_step_compiles_for_a_v5e_and_fits(v5e_2x2, monkeypatch):
    """``kanana2_train_t192``'s train step as its files give it (pattern
    ``L-LELELELE`` at the published widths, B32 x 2p x T192 packed to 8 + 96
    slots, ``remat: block``, bfloat16: two leading layers and a scan over four
    ``LE`` periods) compiles for a described v5e: the grouped kernels take
    experts 768 wide where they lie in the periods' stack, the latent
    attention's forward part runs ``ops/latent_core.py``'s kernel (no float32
    scores of (64, 32, 96, 104) and no re-laid q in the program; the burn-in
    part keeps the einsum lines), the program's peak is under the
    chip's 16.9 GB with room (9.34 GB, 6.22 of it the arguments, 141 MB of
    generated code and 60-80 s of compile alone on this host, PR 56; 9.64 GB
    and 155 MB on the einsum lines, PR 52; unrolled it was 8.78 GB, 384 MB
    and 85 s, and a cold run on the chip left 21 s of its 330: PR 52), and no
    whole leaf of an expert layer's weights, or of their stack, is copied."""
    import json
    import os

    from handyrl_tpu.models.hybrid import MLA_CORE_SCOPE

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "workloads", "kanana2_train_t192.json")) as f:
        cell = json.load(f)
    with open(os.path.join(bench, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    _, lowered = _lowered(v5e_2x2, 1, dict(config["env_args"]),
                          dict(config["train_args"], **cell["train_args"]), packed=(8, 96))
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert 6.0e9 < memory.argument_size_in_bytes < memory.peak_memory_in_bytes < 12.0e9
    text = compiled.as_text()
    # one period in the program: a window part has two products forward, those again
    # under the backward scan, two rows' cotangents and two weight sums; and the forward
    # part's latent attention core (``ops/latent_core.py``, PR 56) in the leading layer
    # and in the period, each forward, replayed under its checkpoint and backward
    assert text.count("tpu_custom_call") == 2 * 8 + 2 * 3
    cores = [line for line in text.splitlines() if "custom-call(" in line
             and "tpu_custom_call" in line and MLA_CORE_SCOPE in line]
    assert len(cores) == 6
    assert "f32[64,32,96,104]" not in text and "bf16[64,96,32,192]" not in text
    held = config["env_args"]["net_args"]["experts_held"]
    copies = re.compile(
        r"= (bf16|f32)\[(4,)?%d,(2048,1536|768,2048)\]\S* (copy|copy-start)\(" % held)
    found = [line.strip()[:160] for line in text.splitlines() if copies.search(line)]
    assert not found, found[:3]
