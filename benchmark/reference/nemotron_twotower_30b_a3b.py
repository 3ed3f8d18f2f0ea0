"""Plain reference of ``nemotron_twotower_30b_a3b``: the tower that the
``nemotron_h`` config.json defines, as a policy trunk, in straightforward
float32 ``jax.numpy``: no flax, no chunks, no kernels, no cache, nothing
imported from ``handyrl_tpu.models``.  Written from the family's published
equations.  The denoiser tower and block-diffusion decoding of the
two-tower model are absent here as in the program (see the configuration's
``departures``).

    x       enc2(ReLU(enc1(flattened observation)))        (this system's encoder)
    layer   x = x + mixer(RMSNorm(x)), eps 1e-5, by the pattern string
    heads   policy, tanh(value), return on RMSNorm_f(x)      (this system's heads)

``M``, Mamba-2, as a recurrence over the steps of one sequence (``lax.scan``):
    [z, xBC, dt] = in_proj(u);  xBC = silu(conv_4(xBC) + bias), causal and
    depthwise over this and the last three observed inputs;  split x (H heads
    of P), B, C (G groups of S; a group serves H/G heads);
    dt = softplus(dt + dt_bias);  A = -exp(A_log);
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t;
    out_proj(groupRMSNorm_G(y * silu(z))).
    A step the player did not observe leaves S and the conv's inputs as they
    were (its output is never read).
``E``, experts: s = sigmoid(W_r h); the k largest of s + b (b chooses only);
    g_i = scale * s_i / sum of the chosen s;  out = sum over the chosen that
    are held here of g_i W2_i relu(W1_i h)^2, plus the shared expert
    W2_s relu(W1_s h)^2.  The experts are a loop over the held ones with dense
    masks.  Told the ``choices``, it uses those experts and still computes
    the gates from its own scores at those indices.
``*``, attention: grouped-query (Hq query heads share Hk key/value heads),
    softmax(q k^T / sqrt(D)) over the observed steps s <= t with fewer than
    ``memory_len`` observed steps between; no bias, no rotary embedding.

Callers set ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

CHOICES = "choices"


def rms_norm(x, scale, eps, groups=1):
    parts = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    parts = parts / jnp.sqrt((parts ** 2).mean(axis=-1, keepdims=True) + eps)
    return parts.reshape(x.shape) * scale


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
    k, scale = int(net["top_k"]), float(net["routed_scale"])
    held, offset = int(net["experts_held"]), int(net["expert_offset"])
    scores = jax.nn.sigmoid(h @ p["router"])
    if chosen is None:
        chosen = jnp.argsort(-(scores + p["score_bias"]), axis=-1)[..., :k].astype(jnp.int32)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = scale * picked / picked.sum(axis=-1, keepdims=True)
    act = lambda v: jnp.maximum(v, 0.0) ** 2  # noqa: E731
    out = act(h @ p["shared_up"]["kernel"]) @ p["shared_down"]["kernel"]
    for e in range(held):
        gate = (gates * (chosen == offset + e)).sum(axis=-1, keepdims=True)
        out = out + gate * (act(h @ p["w1"][e]) @ p["w2"][e])
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
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(width)
    weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", weights, v).reshape(n, t, hq * width) @ p["o"]["kernel"]


def forward(params, obs, observed, config, choices=None):
    """obs: pytree with (N, T, ...) leaves; observed (N, T); choices: None,
    or {layer: (N, T, k)}.  Returns the heads for every step, (N, T, .), and
    under ``choices`` what every routed layer used."""
    net = config["env_args"]["net_args"]
    eps = float(net["norm_eps"])
    dense = lambda p, x: x @ p["kernel"] + p["bias"]  # noqa: E731
    flat = jnp.concatenate(
        [l.reshape(l.shape[:2] + (-1,)).astype(jnp.float32) for l in jax.tree.leaves(obs)],
        axis=-1)
    x = dense(params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    observed = jnp.asarray(observed, jnp.float32)
    used = {}
    for i, kind in enumerate(net["pattern"]):
        name = "layer%d" % i
        p = params[name]
        h = rms_norm(x, p["norm"], eps)
        if kind == "M":
            x = x + mamba(p["mixer"], h, observed, net)
        elif kind == "E":
            y, chosen = experts(p["mixer"], h, net, None if choices is None else choices[name])
            x = x + y
            # a step the player did not observe is no token and chooses nothing
            used[name] = jnp.where(observed[..., None] > 0, chosen, 0)
        else:
            x = x + attention(p["mixer"], h, observed, net)
    h = rms_norm(x, params["norm_f"], eps)
    out = {"policy": dense(params["policy"], h), "value": jnp.tanh(dense(params["value"], h))}
    if "return_head" in params:
        out["return"] = dense(params["return_head"], h)
    out[CHOICES] = used
    return out


def forward_rows(params, batch, config, burn_in, choices=None):
    """The reference on a training batch (B, T, P, ...): each player's window
    is one sequence; returns (B, T - burn_in, P, .) like the train step's
    forward.  ``choices`` are the system's, shaped as its forward returns
    them: {layer: (B, T - burn_in, P, k)} without burn-in; with it
    ``{"forward": ..., "window_start": ...}``, the forward steps' and the
    window's first T - burn_in steps' (the burn-in steps' choices reach the
    forward steps through the state they leave).  Its own are returned in
    the same form."""
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
