"""``ops/ssd.py`` ``ssd_window``: a bfloat16 window part's scan, skip, gate
and group norm as one Pallas kernel a (row, group) (here the Pallas
interpreter, at tiny widths that keep the cell's ratios), against the lines
``Mamba2Mixer`` had before it and keeps for every other part: the output, the
new state and all nine gradients; which parts take which path and why, and
the one-off event that says so; and the rule the kernel was landed under: a
float32 window is the parent's program to the bit (a frozen copy of the
mixer's core as it stood at ``b7b6c56`` is held here to say so).  What the
TPU's compiler says of the kernel is ``tests/test_chip_compile.py``'s.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.models import hybrid
from handyrl_tpu.models.hybrid import Mamba2Mixer
from handyrl_tpu.ops import ssd
from handyrl_tpu.parallel import TrainContext
from handyrl_tpu.parallel.train_step import sub_jaxprs
from handyrl_tpu.utils import trace

N, G, SERVED, P, S, EPS, CHUNK = 2, 2, 2, 8, 16, 1e-5, 128      # the cell: 8 groups of 8 x 64, state 128
H = G * SERVED
NAMES = ("x", "dt", "A", "B", "C", "z", "D", "norm_scale", "state")


# -- the mixer's core as it stood before the kernel (hybrid.py:298-310 and
# ops/ssd.py ``ssd_chunked`` at b7b6c56), frozen: nothing here follows the package


def _frozen_rms(x, scale, eps, groups=1):
    shape = x.shape
    y = x.astype(jnp.float32).reshape(shape[:-1] + (groups, shape[-1] // groups))
    y = y * jax.lax.rsqrt(jnp.square(y).mean(axis=-1, keepdims=True) + eps)
    return (y.reshape(shape) * scale.astype(jnp.float32)).astype(x.dtype)


def _frozen_ssd_chunked(x, dt, A, B, C, state, chunk):
    n = x.shape[0]
    rows = 16
    if n <= rows or n % rows:
        return _frozen_chunks(x, dt, A, B, C, state, chunk)
    split = lambda a: a.reshape((n // rows, rows) + a.shape[1:])  # noqa: E731
    y, state = jax.lax.map(
        lambda part: _frozen_chunks(part[0], part[1], A, part[2], part[3], part[4], chunk),
        tuple(split(a) for a in (x, dt, B, C, state)))
    return y.reshape((n,) + y.shape[2:]), state.reshape((n,) + state.shape[2:])


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _frozen_chunks(x, dt, A, B, C, state, chunk):
    n, length, h, p = x.shape
    g, s = B.shape[2:]
    r = h // g
    q = min(int(chunk), length)
    pad = -length % q
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
        x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
    nc = (length + pad) // q
    x = x.reshape(n, nc, q, g, r, p)
    B, C = B.reshape(n, nc, q, g, s), C.reshape(n, nc, q, g, s)
    dt = jnp.moveaxis(dt.reshape(n, nc, q, h), 3, 2)
    cum = jnp.cumsum(dt * A[None, None, :, None], axis=-1)
    f32 = jnp.float32
    scores = jnp.einsum("ncigs,ncjgs->ncgij", C, B, preferred_element_type=f32)
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weights = decay.reshape(n, nc, g, r, q, q) * scores[:, :, :, None] \
        * dt.reshape(n, nc, g, r, 1, q)
    y = jnp.einsum("ncgrij,ncjgrp->ncigrp", weights.astype(x.dtype), x,
                   preferred_element_type=f32)
    to_end = (dt * jnp.exp(cum[..., -1:] - cum)).reshape(n, nc, g, r, q)
    scaled = (x.astype(f32) * jnp.moveaxis(to_end, 4, 2)[..., None]).astype(x.dtype)
    added = jnp.einsum("ncjgrp,ncjgs->ncgrps", scaled, B, preferred_element_type=f32)
    kept = jnp.exp(cum[..., -1]).reshape(n, nc, g, r, 1, 1)
    state = state.reshape(n, g, r, p, s)
    handed = []
    for c in range(nc):
        handed.append(state)
        state = kept[:, c] * state + added[:, c]
    handed = jnp.stack(handed, axis=1)
    carried = jnp.einsum("ncigs,ncgrps->ncigrp", C, handed.astype(C.dtype),
                         preferred_element_type=f32)
    since = jnp.moveaxis(jnp.exp(cum), 3, 2).reshape(n, nc, q, g, r, 1)
    y = (y + since * carried).reshape(n, nc * q, h, p)[:, :length]
    return y.astype(x.dtype), state.reshape(n, h, p, s)


def frozen_core(x, dt, A, B, C, z, skip, norm_scale, state, eps=EPS, chunk=CHUNK):
    """x (N, L, H, P) ... -> (the mixer's normed output (N, L, H x P) in x's
    dtype, the new state): the scan's lines, the skip, the gate, the norm."""
    n, length, h, p = x.shape
    y, ssm = _frozen_ssd_chunked(x, dt, A, B, C, state, chunk)
    y = y.astype(jnp.float32) + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(n, length, h * p) * jax.nn.silu(z.astype(jnp.float32))
    return _frozen_rms(y, norm_scale, eps, groups=B.shape[2]).astype(x.dtype), ssm


# -- the kernel against the lines ----------------------------------------------


def _operands(length, case, seed=0, n=N, widths=(G, SERVED, P, S)):
    """The core's nine operands in bfloat16: ``fresh`` a state of zeros,
    ``handed`` one handed in, ``valid`` also a prefix mask on dt (a row that
    observes all but five steps, one that observes half)."""
    g, served, p, s = widths
    h = g * served
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    bf = jnp.bfloat16
    dt = jax.nn.softplus(jax.random.normal(k[1], (n, length, h)) - 1.0)
    if case == "valid":
        observed = jnp.array([length - 5, length // 2] * n)[:n]
        dt = dt * (jnp.arange(length)[None, :, None] < observed[:, None, None])
    return (jax.random.normal(k[0], (n, length, h, p), bf), dt,
            -jnp.exp(jax.random.normal(k[2], (h,))),
            (0.5 * jax.random.normal(k[3], (n, length, g, s))).astype(bf),
            (0.5 * jax.random.normal(k[4], (n, length, g, s))).astype(bf),
            jax.random.normal(k[5], (n, length, h * p), bf),
            1.0 + 0.1 * jax.random.normal(k[6], (h,)),
            1.0 + 0.1 * jax.random.normal(k[7], (h * p,)),
            0.5 * jax.random.normal(k[8], (n, h, p, s)) * (case != "fresh"))


def _weighed(fn):
    """``fn``'s two results against fixed random weights (a normed output's
    square sums to a constant), the results beside the sum."""
    def loss(*operands):
        out, new = fn(*operands)
        w = jax.random.split(jax.random.PRNGKey(5))
        return ((out.astype(jnp.float32) * jax.random.normal(w[0], out.shape)).sum()
                + (new * jax.random.normal(w[1], new.shape)).sum()), (out, new)
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(9)), has_aux=True))


# compiled once a length: a length's three cases differ in values alone
_KERNEL = _weighed(lambda *operands: ssd.ssd_window(*operands, EPS))
_LINES = _weighed(frozen_core)


def _far(a, b):
    """The distance of two arrays over the second's norm."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("case", ["fresh", "handed", "valid"])
@pytest.mark.parametrize("length", [16, 24, 96, 184])
def test_the_kernel_is_the_lines_forward_and_in_all_nine_gradients(length, case):
    """A part as one chunk in VMEM (184 steps too, where the lines run two
    chunks of 128) reads what the lines read to bfloat16's rounding: the
    lines themselves stand 2e-3 to 2e-2 from their float32 selves here."""
    operands = _operands(length, case)
    (_, (out, new)), grads = _KERNEL(*operands)
    (_, (want, want_new)), want_grads = _LINES(*operands)
    assert out.dtype == jnp.bfloat16 and new.dtype == jnp.float32
    assert _far(out, want) < 6e-3 and _far(new, want_new) < 1e-3
    far = {name: _far(got, lines) for name, got, lines in zip(NAMES, grads, want_grads)}
    assert max(far.values()) < 2.5e-2, far


def _pallas_calls(jaxpr):
    """(kernel's name, operands) of every ``pallas_call`` in ``jaxpr``, the nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["jaxpr"].debug_info.func_name, len(eqn.invars)))
        for inner in sub_jaxprs(eqn):
            found.extend(_pallas_calls(inner))
    return found


@pytest.mark.parametrize("length", [24, 96])
def test_a_caller_that_drops_the_state_runs_no_program_for_it(length):
    """A train step's forward part hands its last state to nobody: the
    state's kernel is a program of its own that such a caller's compiled
    step does not hold, and the backward kernel, told by the cotangent's
    symbolic zero, is handed no cotangent of it and computes none of its
    terms; the eight gradients that are left are the lines'."""
    operands = _operands(length, "handed")

    def weighed(fn):
        def loss(*operands):
            out, _ = fn(*operands)
            return (out.astype(jnp.float32) * jax.random.normal(
                jax.random.PRNGKey(5), out.shape)).sum()
        return jax.grad(loss, argnums=tuple(range(9)))

    kernel = weighed(lambda *operands: ssd.ssd_window(*operands, EPS))
    grads, want = jax.jit(kernel)(*operands), jax.jit(weighed(frozen_core))(*operands)
    far = {name: _far(got, lines) for name, got, lines in zip(NAMES, grads, want)}
    assert max(far.values()) < 2.5e-2, far
    calls = dict(_pallas_calls(jax.make_jaxpr(kernel)(*operands).jaxpr))
    # as traced: the forward rule's two programs, and a backward of 11 operands and d_out
    assert calls == {"_window_forward_kernel": 11, "_window_state_kernel": 5,
                     "_window_backward_kernel": 12}, calls
    used = dict(_pallas_calls(jax.make_jaxpr(
        lambda *o: _KERNEL.__wrapped__(*o)[1])(*operands).jaxpr))
    assert used["_window_backward_kernel"] == 13, used     # the state's cotangent with them


# (dtype, length, heads, head_dim, groups, state_size) -> a part of the reason, "" for the kernel
CHOICES = [
    ((jnp.float32, 96, 64, 64, 8, 128), "float32, not bfloat16"),
    ((jnp.bfloat16, 8, 64, 64, 8, 128), "8 steps a part, under 16"),       # the cell's burn-in
    ((jnp.bfloat16, 20, 64, 64, 8, 128), "not whole tiles of 8 rows"),
    ((jnp.bfloat16, 96, 4, 8, 2, 16), "not whole tiles of 128 lanes"),      # the tiny nets
    ((jnp.bfloat16, 96, 128, 64, 1, 128), "a group serves 128 heads"),      # granite_4_0_h_small
    ((jnp.bfloat16, 512, 64, 64, 8, 128), "bytes of VMEM"),
    ((jnp.bfloat16, 96, 64, 64, 8, 128), ""),       # the cell's forward part
    ((jnp.bfloat16, 184, 64, 64, 8, 128), ""),      # its judge's unpacked one
]


@pytest.mark.parametrize("operands,why", CHOICES, ids=[
    "float32", "burn_in", "odd_steps", "lanes", "heads", "vmem", "the_cell", "the_judge"])
def test_window_fits_refuses_by_name_and_the_event_says_what_it_chose(operands, why, tmp_path):
    """``window_fits`` decides from dtype and shape, keeps what it decided
    and why, and a context with a tracer on writes it out once as
    ``model.ssd_window_path``."""
    ssd.WINDOW_PATHS.clear()
    assert ssd.window_fits(*operands) == (not why)
    ((key, record),) = ssd.WINDOW_PATHS.items()
    assert key == (jnp.dtype(operands[0]).name,) + operands[1:] and why in record["why"], record
    assert record["path"] == ("lines" if why else "kernel")
    context = types.SimpleNamespace(_attention_paths=set())
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        TrainContext._record_attention_paths(context)
        TrainContext._record_attention_paths(context)       # said once
    finally:
        trace.shutdown()
    (event,) = [r["attrs"] for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
                if r["name"] == "model.ssd_window_path"]
    assert event["plane"] == "learner" and event["path"] == record["path"]
    assert event["why"] == record["why"] and event["dtype"] == key[0]
    assert [event[k] for k in ("length", "heads", "head_dim", "groups", "state_size")] == list(
        operands[1:])
    ssd.WINDOW_PATHS.clear()


# -- the mixer: which parts it hands the kernel, and float32 to the bit ----------

# widths the kernel takes (a group's channels and the state whole 128-lane tiles)
MIXER = dict(d_model=32, heads=4, head_dim=64, groups=2, state_size=128, conv_kernel=4,
             chunk=CHUNK, eps=EPS, dt_min=1e-3, dt_max=0.1, dt_floor=1e-4)


class FrozenMixer(Mamba2Mixer):
    """``Mamba2Mixer`` in window mode with its core as it stood: the same
    parameters under the same names, the lines between ``in_proj`` and
    ``out_proj`` copied from ``b7b6c56``."""

    @hybrid.nn.compact
    def __call__(self, u, state, valid=None):
        H, P, G, S, K = self.heads, self.head_dim, self.groups, self.state_size, self.conv_kernel
        inner, conv_dim = H * P, H * P + 2 * G * S
        n, length = u.shape[:2]
        kept, ones, zeros = self.param_dtype, hybrid.nn.initializers.ones, hybrid.nn.initializers.zeros
        conv_w = self.param("conv_kernel", hybrid.nn.initializers.lecun_normal(), (K, conv_dim), kept)
        conv_b = self.param("conv_bias", zeros, (conv_dim,), kept)
        dt_bias = self.param("dt_bias", zeros, (H,), kept)
        a_log = self.param("A_log", zeros, (H,), kept)
        skip = self.param("D", ones, (H,), kept)
        norm_scale = self.param("norm_scale", ones, (inner,), kept)
        z, xbc, dt = jnp.split(hybrid._dense(inner + conv_dim + H, "in_proj", kept)(u),
                               [inner, inner + conv_dim], axis=-1)
        tail = state["conv"].astype(xbc.dtype)
        fed = jnp.concatenate([tail, xbc], axis=1)
        conv = sum(fed[:, k:k + length] * conv_w[k].astype(xbc.dtype) for k in range(K))
        xbc_c = jax.nn.silu(conv + conv_b.astype(xbc.dtype))
        if valid is None:
            new_tail = fed[:, length:]
        else:
            last = valid.sum(axis=1)[:, None] + jnp.arange(K - 1)[None, :]
            new_tail = jnp.take_along_axis(fed, last[..., None], axis=1)
        x, B, C = jnp.split(xbc_c, [inner, inner + G * S], axis=-1)
        x = x.reshape(n, length, H, P)
        B, C = B.reshape(n, length, G, S), C.reshape(n, length, G, S)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        if valid is not None:
            dt = dt * valid[..., None]
        A = -jnp.exp(a_log.astype(jnp.float32))
        with jax.named_scope("ssd"):
            y, ssm = _frozen_ssd_chunked(x, dt, A, B, C, state["ssm"], self.chunk)
        y = y.astype(jnp.float32) + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        y = y.reshape(n, length, inner) * jax.nn.silu(z.astype(jnp.float32))
        y = _frozen_rms(y, norm_scale, self.eps, groups=G).astype(u.dtype)
        out = hybrid._dense(self.d_model, "out_proj", kept)(y)
        return out, {"ssm": ssm, "conv": new_tail.astype(jnp.float32)}


def _mixer_case(n, length, dtype, seed=3):
    """(parameters, u, state, valid) of a mixer at ``MIXER``'s widths: rows
    that observe a prefix of the part, a state and a conv tail handed in."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    inner, conv_dim = MIXER["heads"] * MIXER["head_dim"], \
        MIXER["heads"] * MIXER["head_dim"] + 2 * MIXER["groups"] * MIXER["state_size"]
    u = jax.random.normal(k[0], (n, length, MIXER["d_model"]), dtype)
    state = {"ssm": 0.5 * jax.random.normal(k[1], (n, MIXER["heads"], MIXER["head_dim"],
                                                   MIXER["state_size"])),
             "conv": jax.random.normal(k[2], (n, MIXER["conv_kernel"] - 1, conv_dim))}
    valid = jnp.arange(length)[None, :] < jax.random.randint(k[3], (n, 1), length // 2, length + 1)
    params = Mamba2Mixer(**MIXER).init(k[4], u[:1], jax.tree.map(lambda a: a[:1], state))["params"]
    return jax.tree.map(lambda a: a.astype(dtype), params), u, state, valid


def _applied(module):
    """``module`` applied and weighed: (out, new state), and the gradients
    of the parameters, the input and the state handed in."""
    def loss(params, u, state, valid):
        out, new = module.apply({"params": params}, u, state, valid)
        w = jax.random.split(jax.random.PRNGKey(7), 3)
        return ((out.astype(jnp.float32) * jax.random.normal(w[0], out.shape)).sum()
                + (new["ssm"] * jax.random.normal(w[1], new["ssm"].shape)).sum()
                + (new["conv"] * jax.random.normal(w[2], new["conv"].shape)).sum()), (out, new)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))


@pytest.mark.parametrize("n,length", [(2, 96), (2, 184), (32, 96)],
                         ids=["one_chunk", "two_chunks", "rows_at_once"])
def test_a_float32_window_is_the_parents_program_to_the_bit(n, length):
    """The rule the kernel was landed under (PERF.md, PR 69): a part that is
    not bfloat16 runs the lines it ran, the same functions in the same
    order, so the benchmark's float32 judge reads what it read on every
    seed.  Output, new state and every gradient equal the frozen copy's
    bit for bit, at one chunk, at two and through the ``ROWS_AT_ONCE`` map;
    the record says ``lines`` and names the dtype."""
    params, u, state, valid = _mixer_case(n, length, jnp.float32)
    ssd.WINDOW_PATHS.clear()
    got = _applied(Mamba2Mixer(**MIXER))(params, u, state, valid)
    want = _applied(FrozenMixer(**MIXER))(params, u, state, valid)
    (record,) = ssd.WINDOW_PATHS.values()
    assert record["path"] == "lines" and "float32" in record["why"] and record["length"] == length
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), got, want)
    assert all(jax.tree.leaves(same)), same
    assert float(jnp.abs(got[1][0]["in_proj"]["kernel"]).sum()) > 0.0
    ssd.WINDOW_PATHS.clear()


@pytest.mark.parametrize("length,path", [(8, "lines"), (24, "kernel")])
def test_a_bfloat16_mixer_hands_the_kernel_its_longer_parts(length, path):
    """The mixer's own wiring: at widths the kernel takes, a bfloat16 part
    of 24 observed-prefix steps goes through it (gate, ``D`` and
    ``norm_scale`` in their places: the result is the frozen mixer's to
    rounding, gradients too), the 8-step burn-in part keeps the lines and
    reads what they read exactly."""
    params, u, state, valid = _mixer_case(2, length, jnp.bfloat16)
    ssd.WINDOW_PATHS.clear()        # ``init`` ran the part in float32
    (_, (out, new)), grads = _applied(Mamba2Mixer(**MIXER))(params, u, state, valid)
    (_, (want, want_new)), want_grads = _applied(FrozenMixer(**MIXER))(params, u, state, valid)
    (record,) = ssd.WINDOW_PATHS.values()
    assert record["path"] == path and record["dtype"] == "bfloat16" and out.dtype == jnp.bfloat16
    far = jax.tree.map(_far, ((out, new), grads), ((want, want_new), want_grads))
    assert max(jax.tree.leaves(far)) <= (3e-2 if path == "kernel" else 0.0), far
    ssd.WINDOW_PATHS.clear()
