"""Plain reference of ``zaya1_8b``: the layer that ZAYA1-8B's config.json
defines (``model_type`` ``zaya``: a compressed convolutional attention
sub-layer, then a top-1 expert sub-layer behind an MLP router that carries
its representation from layer to layer), as a policy trunk, in
straightforward float32 ``jax.numpy``: no flax, no packing, no kernels, no
cache, the convolutions as explicit shifted sums, the experts a loop over
the held ones, nothing imported from ``handyrl_tpu``.  Written from the
equations (arXiv:2510.04476 for the attention, arXiv:2511.17127 for the
router; the configuration's ``assumed`` says what config.json leaves open).

    x       enc2(ReLU(enc1(flattened observation)))        (this system's encoder)
    layer   x = x + CCA(RMSNorm(x));  x = x + MoE(RMSNorm(x)), eps 1e-5
    heads   policy, tanh(value), return on RMSNorm_f(x)      (this system's heads)

``CCA``, with h the normed input and ``[t - j]`` the row's j-th observed step
before step t (a zero row where there is none):
    q~ = h Wq (Hq heads of D), k~ = h Wk (Hk heads of D);
    v = [h_t Wv1; h_[t-1] Wv2], read as Hk heads of D;
    z = [q~; k~];  z1_t = b0 + sum_i w0[i] * z_[t - (K0-1-i)]    (a filter a channel)
    z2_t = b1 + sum_j W1[j] z1_[t - (K1-1-j)]                   (a (D, D) matrix a head);
    before a row's first step z is zero rows (so z1 there is b0);
    q = z2_q + (q~ + k~ of its key head) / 2;
    k = z2_k + (mean of its query heads' q~ + k~) / 2;
    q <- sqrt(D) q / |q|;  k <- temp sqrt(D) k / |k|, a head;
    the first ``rotary`` dimensions of each head turn by the token's index
    among the row's observed steps (pairs (d, d + rotary/2), base theta);
    softmax(q k^T / sqrt(D)) over the observed steps s <= t with fewer than
    ``memory_len`` observed steps between, times v, times Wo.
``MoE``, with h the normed input, l counting the expert sub-layers:
    r_l = h Wd + bd (+ gamma_l * r_{l-1} for l > 0);
    p = softmax(W3 gelu(W2 gelu(W1 RMSNorm(r_l) + b1) + b2));
    e* = argmax(p + b) (b chooses only);  out = p[e*] W2_e*(silu(a) * b'),
    [a, b'] = h W1_e*, added only where e* is held here (``experts_held``
    from ``expert_offset``).  Told the ``choices``, it uses that expert and
    still takes the gate from its own p at that index.
A step the player did not observe is no token: no later step sees it, in the
convolutions, the shifted value or the attention, and its own output is never
read.

Callers set ``jax.default_matmul_precision("highest")``.
"""

import jax
import jax.numpy as jnp
from jax.scipy.special import erf

CHOICES = "choices"


def rms_norm(x, scale, eps):
    return x / jnp.sqrt((x ** 2).mean(axis=-1, keepdims=True) + eps) * scale


def gelu(x):
    return 0.5 * x * (1.0 + erf(x / jnp.sqrt(2.0)))


def rope(x, pos, theta):
    """x (N, T, H, R), pos (N, T): the pair (d, d + R/2) turns by
    ``pos * theta ** (-2 d / R)``."""
    half = x.shape[-1] // 2
    angle = pos[..., None, None] * theta ** (-2.0 * jnp.arange(half) / x.shape[-1])
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * jnp.cos(angle) - second * jnp.sin(angle),
                            second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def back(x, observed, pos, j):
    """x (N, T, ...) -> at step t, x at the row's j-th observed step before
    t; zeros where there is none."""
    if j == 0:
        return x
    pick = (observed[:, None, :] > 0) & (pos[:, None, :] == pos[:, :, None] - j)
    return jnp.einsum("nts,ns...->nt...", pick.astype(x.dtype), x)


def cca(p, h, observed, net):
    """h (N, T, d) normed input, observed (N, T) in {0, 1}."""
    hq, hk, width = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    k0, k1, share = int(net["cca_time0"]), int(net["cca_time1"]), hq // hk
    rotary = 2 * int(width * float(net["rotary_factor"]) / 2)
    n, t, _ = h.shape
    pos = jnp.cumsum(observed, axis=1) - observed          # observed steps before this one
    q_in, k_in = h @ p["q"]["kernel"], h @ p["k"]["kernel"]
    z = jnp.concatenate([q_in, k_in], axis=-1)
    z2 = jnp.zeros((n, t, hq + hk, width)) + p["conv1_bias"].reshape(hq + hk, width)
    for j in range(k1):
        z1 = p["conv0_bias"] + sum(
            p["conv0_kernel"][i] * back(z, observed, pos, (k1 - 1 - j) + (k0 - 1 - i))
            for i in range(k0))
        z2 = z2 + jnp.einsum("ntgd,gde->ntge", z1.reshape(n, t, hq + hk, width),
                             p["conv1_kernel"][j])
    q_in, k_in = q_in.reshape(n, t, hq, width), k_in.reshape(n, t, hk, width)
    q = z2[:, :, :hq] + (q_in + jnp.repeat(k_in, share, axis=2)) / 2
    k = z2[:, :, hq:] + (q_in.reshape(n, t, hk, share, width).mean(axis=3) + k_in) / 2
    q = jnp.sqrt(width) * q / jnp.sqrt((q ** 2).sum(axis=-1, keepdims=True) + 1e-12)
    k = jnp.sqrt(width) * k / jnp.sqrt((k ** 2).sum(axis=-1, keepdims=True) + 1e-12)
    k = k * p["temp"][:, None]
    theta = float(net["rope_theta"])
    turn = lambda x: jnp.concatenate(  # noqa: E731
        [rope(x[..., :rotary], pos, theta), x[..., rotary:]], axis=-1)
    q, k = turn(q), jnp.repeat(turn(k), share, axis=2)
    v = jnp.concatenate([h @ p["v_now"]["kernel"],
                         back(h @ p["v_prev"]["kernel"], observed, pos, 1)], axis=-1)
    v = jnp.repeat(v.reshape(n, t, hk, width), share, axis=2)
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


def experts(p, h, carry, net, chosen=None):
    """h (N, T, d); carry (N, T, router width) or None; chosen (N, T, 1) or
    None -> (out, the chosen, this layer's carry)."""
    held, offset = int(net["experts_held"]), int(net["expert_offset"])
    if int(net["top_k"]) != 1 or int(net.get("shared_width", 0)):
        raise ValueError("zaya1_8b chooses one expert a token and has no shared expert")
    r = h @ p["router_down"] + p["router_down_bias"]
    if carry is not None:
        r = r + p["carry_scale"] * carry
    z = rms_norm(r, p["router_norm"], float(net["norm_eps"]))
    z = gelu(z @ p["router_fc1"] + p["router_fc1_bias"])
    z = gelu(z @ p["router_fc2"] + p["router_fc2_bias"])
    scores = jax.nn.softmax(z @ p["router_out"], axis=-1)
    if chosen is None:
        chosen = jnp.argmax(scores + p["score_bias"], axis=-1)[..., None].astype(jnp.int32)
    gates = float(net.get("routed_scale", 1.0)) * jnp.take_along_axis(scores, chosen, axis=-1)
    width = p["w2"].shape[1]
    out = jnp.zeros_like(h)
    for e in range(held):
        up = h @ p["w1"][e]
        mine = gates * (chosen == offset + e)
        out = out + mine * ((jax.nn.silu(up[..., :width]) * up[..., width:]) @ p["w2"][e])
    return out, chosen, r


def forward(params, obs, observed, config, choices=None):
    """obs: pytree with (N, T, ...) leaves; observed (N, T); choices: None,
    or {layer: (N, T, 1)}.  Returns the heads for every step, (N, T, .), and
    under ``choices`` what every routed layer used."""
    net = config["env_args"]["net_args"]
    eps = float(net["norm_eps"])
    dense = lambda p, x: x @ p["kernel"] + p["bias"]  # noqa: E731
    flat = jnp.concatenate(
        [l.reshape(l.shape[:2] + (-1,)).astype(jnp.float32) for l in jax.tree.leaves(obs)],
        axis=-1)
    x = dense(params["enc2"], jnp.maximum(dense(params["enc1"], flat), 0.0))
    observed = jnp.asarray(observed, jnp.float32)
    used, carry = {}, None
    for i, kind in enumerate(net["pattern"]):
        name = "layer%d" % i
        p = params[name]
        h = rms_norm(x, p["norm"], eps)
        if kind == "C":
            x = x + cca(p["mixer"], h, observed, net)
        elif kind == "E":
            y, chosen, carry = experts(p["mixer"], h, carry, net,
                                       None if choices is None else choices[name])
            x = x + y
            # a step the player did not observe is no token and chooses nothing
            used[name] = jnp.where(observed[..., None] > 0, chosen, 0)
        else:
            raise ValueError("zaya1_8b is 'C' and 'E' sub-layers, not %r" % kind)
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
    them: {layer: (B, T - burn_in, P, 1)} without burn-in; with it
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
