"""Plain reference of ``xfmr_d1536``: this repo's memory transformer over
a whole window, in straightforward float32 ``jax.numpy``: no flax, no
kernels, no cache.  Written from the module's description
(``handyrl_tpu/models/transformer.py``): the window form must give what a
player stepping through the game with a ring of the last ``memory_len``
observed steps would compute.

    x      enc2(ReLU(enc1(flattened observation)))
    layer  x = x + o(attention(LayerNorm_a(x)));  x = x + dn(ReLU(up(LayerNorm_m(x))))
    heads  policy, tanh(value), return on LayerNorm_f(x)

Attention of query step t over key step s of the same sequence: allowed
when s is observed, s <= t and fewer than ``memory_len`` observed steps lie
between (age = observed steps up to t minus observed steps up to s, 0 <=
age < memory_len); a step always sees itself.  The score is
q.k / sqrt(head) - slope_h * age with ALiBi slopes 2^(-8(h+1)/H).

Callers set ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

EPS = 1e-6      # flax LayerNorm's default


def dense(p, x):
    return x @ p["kernel"] + p["bias"]


def layer_norm(p, x):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def attention(p, x, observed, heads, memory_len):
    """x (N, T, d), observed (N, T) in {0, 1}."""
    n, t, d = x.shape
    split = lambda y: y.reshape(n, t, heads, d // heads)  # noqa: E731
    q, k, v = split(dense(p["q"], x)), split(dense(p["k"], x)), split(dense(p["v"], x))
    seen = jnp.cumsum(observed, axis=1)                     # observed steps so far
    age = seen[:, :, None] - seen[:, None, :]               # (N, query, key)
    steps = jnp.arange(t)
    allowed = (
        (observed[:, None, :] > 0) & (steps[:, None] >= steps[None, :])[None]
        & (age >= 0) & (age < memory_len)
    ) | (steps[:, None] == steps[None, :])[None]
    slopes = 2.0 ** (-8.0 * (jnp.arange(heads) + 1) / heads)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) / jnp.sqrt(d // heads)
    scores = scores - slopes[None, :, None, None] * age[:, None]
    scores = jnp.where(allowed[:, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", weights, v).reshape(n, t, d)
    return dense(p["o"], out)


def forward(params, obs, observed, config):
    """obs: pytree with (N, T, ...) leaves; observed (N, T).  Returns the
    heads for every step, (N, T, .)."""
    net = config["env_args"]["net_args"]
    leaves = jax.tree.leaves(obs)
    flat = jnp.concatenate(
        [l.reshape(l.shape[:2] + (-1,)).astype(jnp.float32) for l in leaves], axis=-1)
    x = dense(params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    observed = jnp.asarray(observed, jnp.float32)
    for i in range(int(net["n_layers"])):
        h = layer_norm(params["ln_a%d" % i], x)
        x = x + attention(params["attn%d" % i], h, observed,
                          int(net["n_heads"]), int(net["memory_len"]))
        h = layer_norm(params["ln_m%d" % i], x)
        x = x + dense(params["mlp_dn%d" % i],
                      jnp.maximum(dense(params["mlp_up%d" % i], h), 0.0))
    h = layer_norm(params["ln_f"], x)
    out = {"policy": dense(params["policy"], h),
           "value": jnp.tanh(dense(params["value"], h))}
    if "return_head" in params:
        out["return"] = dense(params["return_head"], h)
    return out


def forward_rows(params, batch, config, burn_in):
    """The reference on a training batch (B, T, P, ...): each player's
    window is one sequence; returns (B, T - burn_in, P, .) like the train
    step's forward."""
    b, t, p = batch["action"].shape[:3]
    to_seq = lambda x: jnp.moveaxis(x, 2, 1).reshape((b * p, t) + x.shape[3:])  # noqa: E731
    obs = jax.tree.map(to_seq, batch["observation"])
    observed = to_seq(batch["observation_mask"])[..., 0]
    out = forward(params, obs, observed, config)
    return {
        k: jnp.moveaxis(v.reshape((b, p, t) + v.shape[2:]), 1, 2)[:, burn_in:]
        for k, v in out.items()
    }
