"""Plain reference of ``granite_4_0_h_small``: one period of the
``granitemoehybrid`` language model as a policy trunk, in straightforward
float32 ``jax.numpy``: no flax, no chunks, no kernels, no cache, nothing
imported from ``handyrl_tpu``.  Written from the family's published
equations (the configuration's file lists each departure).

    x0      embed_scale * enc2(ReLU(enc1(flattened observation)))   (this system's encoder)
    layer   x = x + residual_scale * mixer(RMSNorm(x))        mixer: Mamba-2 or attention
            x = x + residual_scale * (routed(RMSNorm(x)) + shared(RMSNorm(x)))
            (the pattern string spells both sub-layers: ``M``/``*`` then ``E``)
    heads   policy / logits_divisor, tanh(value), return on RMSNorm_f(x)

``M``, Mamba-2, as a recurrence over the steps of one sequence (``lax.scan``):
    [z, xBC, dt] = in_proj(u);  xBC = silu(conv_4(xBC) + bias), causal and
    depthwise over this and the last three observed inputs;  x (H heads of
    P), B, C (G groups of S);  dt = softplus(dt + dt_bias);  A = -exp(A_log);
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t;
    out_proj(RMSNorm(y * silu(z))), the norm over the whole inner width in G
    groups.  A step the player did not observe leaves S and the conv's
    inputs as they were (its output is never read).
``E``, experts: l = h W_r (logits over all experts); S = the k largest;
    g = softmax(l[S]);  out = sum over the chosen that are held here of
    g_e W_out,e (silu(a_e) * b_e) with [a_e, b_e] = W_in,e h, plus the shared
    expert W_out (silu(a) * b), [a, b] = W_in h.  A loop over the held
    experts with dense masks.  Told the ``choices``, it uses those experts
    and still computes the gates from its own logits at those indices.
``*``, attention: grouped-query, softmax(q k^T * attn_score_scale) over the
    observed steps s <= t with fewer than ``memory_len`` observed steps
    between; no positions, no bias.

``forward`` is the whole stack on float32 parameters (the CPU tests, and
``forward_rows`` for a training batch: loss and gradients are ``jax.grad``
through it).  ``forward_by_layer`` is the same mathematics walked a
sub-layer at a time, each sub-layer's parameters cast to float32 inside
its own jitted call: at the published widths a float32 copy of the whole is
18 GB, of one sub-layer at most 1.4.  Callers set
``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

CHOICES = "choices"


def rms_norm(x, scale, eps, groups=1):
    parts = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    parts = parts / jnp.sqrt((parts ** 2).mean(axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * scale


def gated(a_b):
    half = a_b.shape[-1] // 2
    return jax.nn.silu(a_b[..., :half]) * a_b[..., half:]


def mamba(p, u, observed, net):
    """u (N, T, d), observed (N, T) in {0, 1}."""
    heads, width = int(net["mamba_heads"]), int(net["mamba_head_dim"])
    groups, size, taps = int(net["n_groups"]), int(net["state_size"]), int(net["conv_kernel"])
    inner = heads * width
    n = u.shape[0]
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[..., :inner], proj[..., inner:-heads], proj[..., -heads:]
    a = -jnp.exp(p["A_log"])

    def step(carry, inputs):
        state, last = carry                       # (N, H, P, S), (N, taps - 1, C)
        z_t, xbc_t, dt_t, seen = inputs
        fed = jnp.concatenate([last, xbc_t[:, None]], axis=1)
        conv = jax.nn.silu((fed * p["conv_kernel"][None]).sum(axis=1) + p["conv_bias"])
        x = conv[:, :inner].reshape(n, heads, width)
        b = jnp.repeat(conv[:, inner:inner + groups * size].reshape(n, groups, size),
                       heads // groups, axis=1)
        c = jnp.repeat(conv[:, inner + groups * size:].reshape(n, groups, size),
                       heads // groups, axis=1)
        dt_t = jax.nn.softplus(dt_t + p["dt_bias"])                   # (N, H)
        new = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x)[..., None] * b[:, :, None, :]
        y = jnp.einsum("nhps,nhs->nhp", new, c) + p["D"][None, :, None] * x
        y = rms_norm(y.reshape(n, inner) * jax.nn.silu(z_t), p["norm_scale"],
                     float(net["norm_eps"]), groups)
        keep = seen[:, None, None, None] > 0
        return (jnp.where(keep, new, state), jnp.where(keep[..., 0], fed[:, 1:], last)), y

    start = (jnp.zeros((n, heads, width, size)), jnp.zeros((n, taps - 1, xbc.shape[-1])))
    inputs = tuple(jnp.moveaxis(v, 1, 0) for v in (z, xbc, dt, observed))
    _, y = jax.lax.scan(step, start, inputs)
    return jnp.moveaxis(y, 0, 1) @ p["out_proj"]["kernel"]


def experts(p, h, net, chosen=None):
    """h (N, T, d); chosen (N, T, k) or None -> (out, the chosen)."""
    k = int(net["top_k"])
    held, offset = int(net["experts_held"]), int(net["expert_offset"])
    logits = h @ p["router"]
    if chosen is None:
        chosen = jnp.argsort(-logits, axis=-1)[..., :k].astype(jnp.int32)
    gates = jax.nn.softmax(jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
    out = gated(h @ p["shared_up"]["kernel"]) @ p["shared_down"]["kernel"]
    for e in range(held):
        gate = (gates * (chosen == offset + e)).sum(axis=-1, keepdims=True)
        out = out + gate * (gated(h @ p["w1"][e]) @ p["w2"][e])
    return out, chosen


def attention(p, h, observed, net):
    hq, hk, width = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    n, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(n, t, hq, width)
    k = jnp.repeat((h @ p["k"]["kernel"]).reshape(n, t, hk, width), hq // hk, axis=2)
    v = jnp.repeat((h @ p["v"]["kernel"]).reshape(n, t, hk, width), hq // hk, axis=2)
    seen = jnp.cumsum(observed, axis=1)
    age = seen[:, :, None] - seen[:, None, :]               # (N, query, key)
    steps = jnp.arange(t)
    allowed = (
        (observed[:, None, :] > 0) & (steps[:, None] >= steps[None, :])[None]
        & (age < int(net["memory_len"]))
    ) | (steps[:, None] == steps[None, :])[None]
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) * float(net["attn_score_scale"])
    weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", weights, v).reshape(n, t, hq * width) @ p["o"]["kernel"]


def dense(p, x):
    return x @ p["kernel"] + p["bias"]


def encode(params, obs, net):
    """obs: pytree with (N, T, ...) leaves -> x0 (N, T, d)."""
    flat = jnp.concatenate(
        [leaf.reshape(leaf.shape[:2] + (-1,)).astype(jnp.float32)
         for leaf in jax.tree.leaves(obs)], axis=-1)
    x = dense(params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    return float(net["embed_scale"]) * x


def sublayer(p, kind, x, observed, net, chosen=None):
    """One sub-layer of the pattern on its own parameters ``p``: -> (x, the
    experts an ``E`` used, else None)."""
    h = rms_norm(x, p["norm"], float(net["norm_eps"]))
    used = None
    if kind == "M":
        y = mamba(p["mixer"], h, observed, net)
    elif kind == "E":
        y, used = experts(p["mixer"], h, net, chosen)
        # a step the player did not observe is no token and chooses nothing
        used = jnp.where(observed[..., None] > 0, used, 0)
    elif kind == "*":
        y = attention(p["mixer"], h, observed, net)
    else:
        raise ValueError(f"no sub-layer of kind {kind!r} in this family")
    return x + float(net["residual_scale"]) * y, used


def heads(params, x, net):
    h = rms_norm(x, params["norm_f"], float(net["norm_eps"]))
    out = {"policy": dense(params["policy"], h) / float(net["logits_divisor"]),
           "value": jnp.tanh(dense(params["value"], h))}
    if "return_head" in params:
        out["return"] = dense(params["return_head"], h)
    return out


def _walk(params, obs, observed, config, choices, call):
    """The stack through ``call(piece, parameters, *arrays)``, ``piece``
    "encode", "heads" or a sub-layer's kind."""
    net = config["env_args"]["net_args"]
    observed = jnp.asarray(observed, jnp.float32)
    x = call("encode", {k: params[k] for k in ("enc1", "enc2")}, obs)
    used = {}
    for i, kind in enumerate(net["pattern"]):
        name = "layer%d" % i
        given = None if choices is None or kind != "E" else choices[name]
        x, chosen = call(kind, params[name], x, observed, given)
        if chosen is not None:
            used[name] = chosen
    last = {k: params[k] for k in ("norm_f", "policy", "value", "return_head") if k in params}
    out = call("heads", last, x)
    out[CHOICES] = used
    return out


def _pieces(net):
    pieces = {"encode": lambda p, o: encode(p, o, net), "heads": lambda p, x: heads(p, x, net)}
    for kind in "ME*":
        pieces[kind] = (
            lambda p, x, seen, given, kind=kind: sublayer(p, kind, x, seen, net, given))
    return pieces


def forward(params, obs, observed, config, choices=None):
    """obs: pytree with (N, T, ...) leaves; observed (N, T); choices: None,
    or {layer: (N, T, k)}.  Returns the heads for every step, (N, T, .), and
    under ``choices`` what every routed layer used."""
    pieces = _pieces(config["env_args"]["net_args"])
    return _walk(params, obs, observed, config, choices,
                 lambda piece, p, *a: pieces[piece](p, *a))


def forward_by_layer(params, obs, observed, config, choices=None):
    """``forward``, each piece a jitted call of its own that casts its own
    parameters to float32 inside (one program a kind of sub-layer): for
    parameters held in a narrower type at widths where a float32 copy of the
    whole does not fit beside them."""
    def widened(fn):
        return jax.jit(lambda p, *a: fn(jax.tree.map(lambda w: w.astype(jnp.float32), p), *a))

    pieces = {piece: widened(fn) for piece, fn in _pieces(config["env_args"]["net_args"]).items()}
    return _walk(params, obs, observed, config, choices,
                 lambda piece, p, *a: pieces[piece](p, *a))


def forward_rows(params, batch, config, burn_in, choices=None):
    """The reference on a training batch (B, T, P, ...): each player's window
    is one sequence; returns (B, T - burn_in, P, .) like the train step's
    forward.  ``choices`` are the system's, shaped as its forward returns
    them: {layer: (B, T - burn_in, P, k)} without burn-in; with it
    ``{"forward": ..., "window_start": ...}`` (the burn-in steps' choices
    reach the forward steps through the state they leave).  Its own are
    returned in the same form."""
    b, t, p = batch["action"].shape[:3]
    kept = t - burn_in
    to_seq = lambda x: jnp.moveaxis(x, 2, 1).reshape((b * p, t) + x.shape[3:])  # noqa: E731
    to_rows = lambda v: jnp.moveaxis(v.reshape((b, p, t) + v.shape[2:]), 1, 2)  # noqa: E731
    obs = jax.tree.map(to_seq, batch["observation"])
    observed = to_seq(batch["observation_mask"])[..., 0]
    given = choices
    if choices is not None and burn_in:
        if kept < burn_in:
            raise ValueError("the window's first forward_steps steps do not hold the burn-in steps")
        choices = {k: jnp.concatenate([choices["window_start"][k][:, :burn_in], v], axis=1)
                   for k, v in choices["forward"].items()}
    if choices is not None:
        choices = {k: to_seq(v) for k, v in choices.items()}
    out = forward(params, obs, observed, config, choices)
    used = out.pop(CHOICES)
    out = {k: to_rows(v)[:, burn_in:] for k, v in out.items()}
    if given is not None:
        out[CHOICES] = given
    elif burn_in:
        out[CHOICES] = {"forward": {k: to_rows(v)[:, burn_in:] for k, v in used.items()},
                        "window_start": {k: to_rows(v)[:, :kept] for k, v in used.items()}}
    else:
        out[CHOICES] = {k: to_rows(v) for k, v in used.items()}
    return out
