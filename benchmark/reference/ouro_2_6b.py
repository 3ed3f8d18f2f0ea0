"""Plain reference of ``ouro_2_6b``: the looped transformer that Ouro-2.6B's
config.json defines (``model_type`` ``ouro``), as a policy trunk, in
straightforward float32 ``jax.numpy``: no flax, no packing, no cache, no
checkpoints, nothing imported from ``handyrl_tpu.models``.  Written from the
equations (arXiv:2510.25741 section 3; the configuration's ``assumed`` says
what config.json leaves open).

    h_0     enc2(ReLU(enc1(flattened observation)))         (this system's encoder)
    for t in 1..T (``total_ut_steps``), over the same weights:
        z = h_{t-1}
        for i in 1..N:                                      (one layer: two sandwiched sub-layers)
            a = RMSNorm(z);  q, k, v = a Wq, a Wk, a Wv     (H heads of D, no bias)
            q, k = rope(q, p), rope(k, p)                   (theta over all D, pairs (d, d + D/2))
            z = z + RMSNorm(softmax(q k^T / sqrt(D) + causal) v Wo)
            m = RMSNorm(z)
            z = z + RMSNorm((silu(m Wg) * (m Wu)) Wd)
        h_t = RMSNorm_f(z)                                  (the one final norm closes every pass)
        g_t = sigmoid(h_t w + b)                            (the exit gate)
    exit_t = g_t prod_{j<t}(1 - g_j) for t < T;  exit_T = prod_{j<T}(1 - g_j)
    heads   policy, tanh(value), return on h_T               (this system's heads)

``p`` is a token's index among its row's *observed* steps; a step the player
did not observe is no token: no later step attends to it, and its own output
is never read (it attends to the observed steps before it and to itself, so
that its softmax is over something).  Attention is dense over the whole
window: ``use_sliding_window`` false.

In the parameter tree the 2N sub-layers are ``layer0 .. layer{2N-1}``,
attention at the even places (the system's pattern string ``"*-" * N``).

Callers set ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (N, T, H, D), pos (N, T): the pair (d, d + D/2) turns by
    ``pos * theta ** (-2 d / D)``."""
    half = x.shape[-1] // 2
    angle = pos[..., None, None] * theta ** (-2.0 * jnp.arange(half) / x.shape[-1])
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * jnp.cos(angle) - second * jnp.sin(angle),
                            second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def attention(p, a, observed, net):
    """a (N, T, d) normed input, observed (N, T) in {0, 1}."""
    heads, width = int(net["n_heads"]), int(net["head_dim"])
    if int(net["n_kv_heads"]) != heads:
        raise ValueError("ouro_2_6b has as many key/value heads as query heads")
    n, t, _ = a.shape
    pos = jnp.cumsum(observed, axis=1) - observed          # observed steps before this one
    theta = float(net["rope_theta"])
    q = rope((a @ p["q"]["kernel"]).reshape(n, t, heads, width), pos, theta)
    k = rope((a @ p["k"]["kernel"]).reshape(n, t, heads, width), pos, theta)
    v = (a @ p["v"]["kernel"]).reshape(n, t, heads, width)
    steps = jnp.arange(t)
    allowed = ((observed[:, None, :] > 0) & (steps[:, None] >= steps[None, :])[None]) \
        | (steps[:, None] == steps[None, :])[None]
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(width)
    weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nhqk,nkhd->nqhd", weights, v).reshape(n, t, heads * width) @ p["o"]["kernel"]


def gated_mlp(p, m):
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) @ p["down"]["kernel"]


def layer(attn, mlp, z, observed, net):
    eps = float(net["norm_eps"])
    z = z + rms_norm(attention(attn["mixer"], rms_norm(z, attn["norm"], eps), observed, net),
                     attn["norm_out"], eps)
    return z + rms_norm(gated_mlp(mlp["mixer"], rms_norm(z, mlp["norm"], eps)),
                        mlp["norm_out"], eps)


def forward(params, obs, observed, config):
    """obs: pytree with (N, T, ...) leaves; observed (N, T).  Returns the
    heads for every step, (N, T, .), and ``exit`` (N, T, passes): the share
    of each token that leaves the loop after each pass."""
    net = config["env_args"]["net_args"]
    passes, n_layers = int(net["loops"]), len(net["pattern"]) // 2
    if net["pattern"] != "*-" * n_layers or not net["sandwich"]:
        raise ValueError("ouro_2_6b is sandwiched layers of attention then a gated MLP")
    dense = lambda p, x: x @ p["kernel"] + p["bias"]  # noqa: E731
    flat = jnp.concatenate(
        [l.reshape(l.shape[:2] + (-1,)).astype(jnp.float32) for l in jax.tree.leaves(obs)],
        axis=-1)
    h = dense(params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    observed = jnp.asarray(observed, jnp.float32)
    stay, leave = jnp.ones(h.shape[:2]), []
    for t in range(passes):
        z = h
        for i in range(n_layers):
            z = layer(params["layer%d" % (2 * i)], params["layer%d" % (2 * i + 1)], z, observed, net)
        h = rms_norm(z, params["norm_f"], float(net["norm_eps"]))
        if t < passes - 1:
            g = jax.nn.sigmoid(dense(params["exit_gate"], h)[..., 0])
            leave.append(stay * g)
            stay = stay * (1 - g)
    out = {"policy": dense(params["policy"], h), "value": jnp.tanh(dense(params["value"], h))}
    if "return_head" in params:
        out["return"] = dense(params["return_head"], h)
    out["exit"] = jnp.stack(leave + [stay], axis=-1)
    return out


def forward_rows(params, batch, config, burn_in):
    """The reference on a training batch (B, T, P, ...): each player's window
    is one sequence; returns the heads (B, T - burn_in, P, .) like the train
    step's forward.  Burn-in steps are steps like any other here: they differ
    in what the gradient reaches, not in what the forward computes."""
    b, t, p = batch["action"].shape[:3]
    to_seq = lambda x: jnp.moveaxis(x, 2, 1).reshape((b * p, t) + x.shape[3:])  # noqa: E731
    to_rows = lambda v: jnp.moveaxis(v.reshape((b, p, t) + v.shape[2:]), 1, 2)  # noqa: E731
    out = forward(params, jax.tree.map(to_seq, batch["observation"]),
                  to_seq(batch["observation_mask"])[..., 0], config)
    out.pop("exit")
    return {k: to_rows(v)[:, burn_in:] for k, v in out.items()}
