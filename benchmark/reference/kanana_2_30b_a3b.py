"""Plain reference of ``kanana_2_30b_a3b``: the layer that
kanana-2-30b-a3b-instruct-2601's config.json defines (``model_type``
``deepseek_v3``, ``q_lora_rank`` null: multi-head latent attention, then a
dense SwiGLU in the leading layer and a top-6-of-128 sigmoid-routed SwiGLU
expert sub-layer with a shared expert in the others), as a policy trunk, in
straightforward float32 ``jax.numpy``: no flax, no packing, no kernels, no
cache, the attention a loop over the query steps in the *expanded* form with
one key of ``qk_nope + qk_rope`` dimensions a head, the experts a loop over
the held ones, nothing imported from ``handyrl_tpu``.

    x       enc2(ReLU(enc1(flattened observation)))        (this system's encoder)
    layer   x = x + MLA(RMSNorm(x));  x = x + FFN(RMSNorm(x)), eps 1e-6
    heads   policy, tanh(value), return on RMSNorm_f(x)      (this system's heads)

``MLA``, with h the normed input and p a token's index among its row's
observed steps, H heads:
    q = h Wq, a head [qn (nope); qr (rope)];
    [c~; kr~] = h Wkva;  c = RMSNorm(c~) (the latent);
    a head's [kn; v] = c Wkvb (nope and v_head wide);
    qr and kr~ turn by p: the pair (2j, 2j + 1) by p * theta ** (-2j / rope);
    k = [kn; kr] (kr the same for every head), q = [qn; qr];
    softmax(q k^T / sqrt(nope + rope)) over the observed steps s <= t with
    fewer than ``memory_len`` observed steps between, times v, times Wo.
``FFN`` of layer 0 (``-``): Wdown(silu(h Wgate) * (h Wup)).  Of the others
(``E``): sc = sigmoid(h Wg); the ``top_k`` experts with the largest sc + b
(b chooses only); w_e = routed_scale * sc_e / (sum of the chosen's sc +
1e-20); out = sum_e w_e W2_e(silu(a) * b'), [a, b'] = h W1_e, over the
experts held here (``experts_held`` from ``expert_offset``), plus the shared
expert Wsd(silu(a) * b'), [a, b'] = h Wsu.  Told the ``choices``, it uses
those experts and still takes the weights from its own scores.
A step the player did not observe is no token: no later step sees it, and its
own output is never read.

Departures from the HF ``DeepseekV3`` modelling code: it permutes the rotated
parts from adjacent pairs to halves and turns those (``rope_interleave``):
the same rotation in another order of the 64, which the scores cannot tell;
it repeats ``kr`` to every head and concatenates on the key side too (here
the repeat is the only broadcast); its shared expert is one MLP of width
``n_shared_experts x moe_intermediate_size`` with separate gate and up
matrices (here their columns side by side in one); ``n_group`` 1 and
``topk_group`` 1 make its group limit the identity, so none is written.

Callers set ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp

CHOICES = "choices"


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(axis=-1, keepdims=True) + eps) * scale


def rope(x, pos):
    """x (N, T, H, R), pos (N, T, R / 2) angles: the pair (2j, 2j + 1) turns
    by ``pos[..., j]``."""
    even, odd = x[..., 0::2], x[..., 1::2]
    cos, sin = jnp.cos(pos)[:, :, None], jnp.sin(pos)[:, :, None]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def turn(x, nope, angle):
    """A head's ``[unrotated (nope); rotated]``: the second part turned."""
    return jnp.concatenate([x[..., :nope], rope(x[..., nope:], angle)], axis=-1)


def shared_key(part, angle, heads):
    """part (N, T, R) -> (N, T, H, R): one rotated key part, every head's."""
    return jnp.repeat(rope(part[:, :, None], angle), heads, axis=2)


def swiglu(h, fused, down):
    """``down(silu(a) * b)``, ``[a, b] = h fused``."""
    up = h @ fused
    width = up.shape[-1] // 2
    return (jax.nn.silu(up[..., :width]) * up[..., width:]) @ down


def score_scale(net):
    return 1.0 / jnp.sqrt(float(int(net["qk_nope_dim"]) + int(net["qk_rope_dim"])))


def mla(p, h, observed, net):
    """h (N, T, d) normed input, observed (N, T) in {0, 1}."""
    heads, nope, turned = int(net["n_heads"]), int(net["qk_nope_dim"]), int(net["qk_rope_dim"])
    wide, latent = int(net["v_head_dim"]), int(net["kv_latent"])
    n, t, _ = h.shape
    q = (h @ p["q"]["kernel"]).reshape(n, t, heads, nope + turned)
    down = h @ p["kv_a"]["kernel"]
    c = rms_norm(down[..., :latent], p["kv_norm"], float(net["norm_eps"]))
    kv = (c @ p["kv_b"]).reshape(n, t, heads, nope + wide)
    pos = jnp.cumsum(observed, axis=1) - observed          # observed steps before this one
    angle = pos[..., None] * float(net["rope_theta"]) ** (
        -2.0 * jnp.arange(turned // 2) / turned)
    q = turn(q, nope, angle)
    k = jnp.concatenate([kv[..., :nope], shared_key(down[..., latent:], angle, heads)], axis=-1)
    v = kv[..., nope:]
    seen = jnp.cumsum(observed, axis=1)
    steps = jnp.arange(t)

    def one(step):      # the step's query against every key it may see
        allowed = ((observed > 0) & (steps[None, :] <= step)
                   & (seen[:, step, None] - seen < int(net["memory_len"]))
                   ) | (steps[None, :] == step)
        scores = jnp.einsum("nhd,nkhd->nhk", q[:, step], k) * score_scale(net)
        weights = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("nhk,nkhd->nhd", weights, v)

    out = jnp.moveaxis(jax.lax.map(one, steps), 0, 1)       # (N, T, H, v_head)
    return out.reshape(n, t, heads * wide) @ p["o"]["kernel"]


def gates(scores, chosen, net):
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return float(net["routed_scale"]) * picked / (picked.sum(axis=-1, keepdims=True) + 1e-20)


def experts(p, h, net, chosen=None):
    """h (N, T, d); chosen (N, T, k) or None -> (out, the chosen)."""
    k = int(net["top_k"])
    held, offset = int(net["experts_held"]), int(net["expert_offset"])
    scores = jax.nn.sigmoid(h @ p["router"])
    if chosen is None:
        chosen = jnp.argsort(-(scores + p["score_bias"]), axis=-1)[..., :k].astype(jnp.int32)
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
    x = dense(params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    observed = jnp.asarray(observed, jnp.float32)
    used = {}
    for i, kind in enumerate(net["pattern"]):
        name = "layer%d" % i
        p = params[name]
        h = rms_norm(x, p["norm"], eps)
        if kind == "L":
            x = x + mla(p["mixer"], h, observed, net)
        elif kind == "-":
            m = p["mixer"]
            x = x + (jax.nn.silu(h @ m["gate"]["kernel"]) * (h @ m["up"]["kernel"])
                     ) @ m["down"]["kernel"]
        elif kind == "E":
            y, chosen = experts(p["mixer"], h, net,
                                None if choices is None else choices[name])
            x = x + y
            # a step the player did not observe is no token and chooses nothing
            used[name] = jnp.where(observed[..., None] > 0, chosen, 0)
        else:
            raise ValueError("kanana_2_30b_a3b is 'L', '-' and 'E' sub-layers, not %r" % kind)
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
