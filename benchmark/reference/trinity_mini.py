"""Plain reference of ``trinity_mini``: the layer that Trinity-Mini's
config.json defines (``model_type`` ``afmoe``) with what its family's
published modelling code (``transformers`` ``models/afmoe/modeling_afmoe.py``)
adds to it, as a policy trunk, in straightforward float32 ``jax.numpy``: no
flax, no packing, no kernels, no ring, the attention a loop over the query
steps, the experts a loop over the held ones, nothing imported from
``handyrl_tpu``.

    x       embed_scale * enc2(ReLU(enc1(flattened observation)))   (this system's encoder)
    layer   x = x + RMS_a2(Attn(RMS_a1(x)));  x = x + RMS_m2(MLP(RMS_m1(x))), eps 1e-5
    heads   policy, tanh(value), return on RMS_f(x)                   (this system's heads)

``Attn`` (``W`` a ``sliding_attention`` layer, ``*`` a ``full_attention``
one), with h the normed input and p a token's index among its row's observed
steps, 32 query heads over 4 key heads:
    q = h Wq, k = h Wk, v = h Wv, g = h Wg (as wide as q);
    every head of q and of k normed over its own ``head_dim`` values (one
    scale for the heads of q, one for those of k);
    in a ``W`` layer q and k then turn by p: d pairs with d + D/2, by
    p * theta ** (-d / (D/2)); in a ``*`` layer nothing turns;
    query head h reads key head h // (heads / kv_heads);
    softmax(q k^T / sqrt(D)) over the observed steps s <= t, in a ``W`` layer
    those fewer than ``window`` observed steps back, times v;
    out = (that, times sigmoid(g)) Wo.
Either kind sees at most ``memory_len`` observed steps back, which is what the
system keeps of a game when it acts (no Geister game has more).
``MLP`` of a ``-`` sub-layer: Wdown(silu(h Wgate) * (h Wup)).  Of an ``E``
one: sc = sigmoid(h Wr); the ``top_k`` experts with the largest sc + b (b
chooses only); w_e = routed_scale * sc_e / (sum of the chosen's sc + 1e-20);
out = sum_e w_e W2_e(silu(a) * b'), [a, b'] = h W1_e, over the experts held
here (``experts_held`` from ``expert_offset``), plus the shared expert
Wsd(silu(a) * b'), [a, b'] = h Wsu.  Told the ``choices``, it uses those
experts and still takes the weights from its own scores.
A step the player did not observe is no token: no later step sees it, and its
own output is never read.

Callers set ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

CHOICES = "choices"


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(axis=-1, keepdims=True) + eps) * scale


def turned(x, pos, theta):
    """x (N, T, H, D) turned at pos (N, T): dimension d pairs with d + D/2."""
    half = x.shape[-1] // 2
    angle = pos[:, :, None, None] * theta ** (-jnp.arange(half) / half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def prepared(x, scale, pos, local, net):
    """Queries or keys (N, T, H, D) as the scores read them: each head
    normed, then in a local layer turned at its position."""
    x = rms_norm(x, scale, float(net["norm_eps"]))
    return turned(x, pos, float(net["rope_theta"])) if local else x


def reach(local, net):
    """The observed steps back a query sees, its own included."""
    return min(int(net["window"]), int(net["memory_len"])) if local else int(net["memory_len"])


def closed(mix, g, p):
    """The core's output (N, T, heads x D) through the gate, then ``o``."""
    return (mix * jax.nn.sigmoid(g)) @ p["o"]["kernel"]


def attention(p, h, observed, local, net):
    """h (N, T, d) normed input, observed (N, T) in {0, 1}."""
    heads, kv_heads, dim = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    n, t, _ = h.shape
    pos = jnp.cumsum(observed, axis=1) - observed          # observed steps before this one
    q = prepared((h @ p["q"]["kernel"]).reshape(n, t, heads, dim), p["q_norm"], pos, local, net)
    k = prepared((h @ p["k"]["kernel"]).reshape(n, t, kv_heads, dim), p["k_norm"], pos, local, net)
    v = (h @ p["v"]["kernel"]).reshape(n, t, kv_heads, dim)
    # every query head its own copy of the key head it reads
    k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
    seen = jnp.cumsum(observed, axis=1)
    steps = jnp.arange(t)

    def one(step):      # the step's query against every key it may see
        allowed = ((observed > 0) & (steps[None, :] <= step)
                   & (seen[:, step, None] - seen < reach(local, net))
                   ) | (steps[None, :] == step)
        scores = jnp.einsum("nhd,nkhd->nhk", q[:, step], k) / jnp.sqrt(float(dim))
        weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhk,nkhd->nhd", weights, v)

    mix = jnp.moveaxis(jax.lax.map(one, steps), 0, 1)       # (N, T, H, D)
    return closed(mix.reshape(n, t, heads * dim), h @ p["gate"]["kernel"], p)


def swiglu(h, fused, down):
    """``down(silu(a) * b)``, ``[a, b] = h fused``."""
    up = h @ fused
    width = up.shape[-1] // 2
    return (jax.nn.silu(up[..., :width]) * up[..., width:]) @ down


def chosen_of(scores, bias, net):
    return jnp.argsort(-(scores + bias), axis=-1)[..., :int(net["top_k"])].astype(jnp.int32)


def gates(scores, chosen, net):
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return float(net["routed_scale"]) * picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)


def experts(p, h, net, chosen=None):
    """h (N, T, d); chosen (N, T, k) or None -> (out, the chosen)."""
    held, offset = int(net["experts_held"]), int(net["expert_offset"])
    scores = jax.nn.sigmoid(h @ p["router"])
    if chosen is None:
        chosen = chosen_of(scores, p["score_bias"], net)
    weights = gates(scores, chosen, net)
    out = swiglu(h, p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    for e in range(held):
        mine = (weights * (chosen == offset + e)).sum(axis=-1, keepdims=True)
        out = out + mine * swiglu(h, p["w1"][e], p["w2"][e])
    return out, chosen


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
    x = float(net["embed_scale"]) * dense(
        params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    observed = jnp.asarray(observed, jnp.float32)
    used = {}
    for i, kind in enumerate(net["pattern"]):
        name = "layer%d" % i
        p = params[name]
        h = rms_norm(x, p["norm"], eps)
        if kind in "W*":
            y = attention(p["mixer"], h, observed, kind == "W", net)
        elif kind == "-":
            m = p["mixer"]
            y = (jax.nn.silu(h @ m["gate"]["kernel"]) * (h @ m["up"]["kernel"])
                 ) @ m["down"]["kernel"]
        elif kind == "E":
            y, chosen = experts(p["mixer"], h, net,
                                None if choices is None else choices[name])
            # a step the player did not observe is no token and chooses nothing
            used[name] = jnp.where(observed[..., None] > 0, chosen, 0)
        else:
            raise ValueError("trinity_mini is 'W', '*', '-' and 'E' sub-layers, not %r" % kind)
        x = x + rms_norm(y, p["norm_out"], eps)
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
    window's first T - burn_in steps'.  Its own are returned in the same
    form."""
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
