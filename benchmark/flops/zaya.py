"""Operations and bytes one update of a ``zaya`` trunk (compressed
convolutional attention sub-layers, ``C``, and top-1 expert sub-layers behind
an MLP router, ``E``) needs as a policy trunk, from shapes.

Counted per token: the multiply-adds of the two encoder layers, of every
sub-layer's products by its kind, and of the heads.  A token is a step of a
player's window that carries an observation: ``shapes.observed_share`` of
the forward steps and ``shapes.observed_share_burn_in`` of the burn-in steps
(the traffic is ``nemotron_h.py``'s and ``ouro.py``'s).  The program runs its
dense products over the packed array's padding and its experts over every
slot of a row buffer; that is work it does, not work the update needs, so no
term counts padding, a checkpoint's replay or an empty buffer slot.

* ``C``: the q, k, two value and o projections; the grouped convolution (a
  (D, D) matrix a head and step); the depthwise one; the scores and the mix
  over the keys a token sees: causal over its row's tokens, so (tokens + 1)
  / 2 on average, at most ``memory_len``.
* ``E``: the router (its down-projection, two hidden maps and output map)
  and the routed rows that fall on held experts: ``top_k x experts_held /
  n_experts`` of a row a token (a uniform router's share), three products
  each (the fused gate-and-up, and down).

2 FLOP a multiply-add; a trained token costs forward once and backward
twice, a burn-in token forward only.  Not counted: norms, the q-k mean, the
L2 norms, rotations, softmax, GELU, sorting, gathers, the loss, the
optimizer.

Bytes: the least HBM traffic: parameters read twice in the compute type,
gradients written and read once in float32, parameters and Adam's two
moments read and written once in float32, and each sub-layer's saved
activations written and read once in the compute type.

``scope_work`` gives the same counts inside the scopes ``experts`` (the
routed rows' grouped products, with ``rows``: the routed rows an update the
count stands for, so that a reader holding ``counter_rows_held`` can
rescale) and ``cca_mix`` (the two convolutions: what the new mechanism adds
between the projections and the attention core).
"""


def _net(config):
    return config["env_args"]["net_args"]


def _sizes(net):
    d = int(net["d_model"])
    q, kv, head = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    return d, q, kv, head, (q + kv) * head


def cca_mix_macs_per_token(net):
    """The grouped convolution and the depthwise one."""
    _, _, _, head, latent = _sizes(net)
    return int(net["cca_time1"]) * latent * head + int(net["cca_time0"]) * latent


def router_macs_per_token(net):
    d, wide = int(net["d_model"]), int(net["router_width"])
    return d * wide + 2 * wide * wide + wide * int(net["n_experts"])


def routed_rows_per_token(net):
    return int(net["top_k"]) * int(net["experts_held"]) / int(net["n_experts"])


def layer_macs_per_token(net, kind, keys):
    d, q, kv, head, latent = _sizes(net)
    if kind == "C":
        return (d * (latent + kv * head) + q * head * d + cca_mix_macs_per_token(net)
                + 2 * keys * q * head)
    if kind == "E":
        return (router_macs_per_token(net)
                + routed_rows_per_token(net) * 3 * d * int(net["expert_width"]))
    raise ValueError(f"flops/zaya.py counts 'C' and 'E' layers, not {kind!r}")


def parameters(net, obs_width, actions, heads_out):
    d, q, kv, head, latent = _sizes(net)
    wide, experts = int(net["router_width"]), int(net["n_experts"])
    n_e = net["pattern"].count("E")
    each = {
        "C": d + d * (latent + kv * head) + q * head * d + (int(net["cca_time0"]) + 1) * latent
        + (int(net["cca_time1"]) * head + 1) * latent + kv,
        "E": d + (d + 2) * wide + 2 * (wide + 1) * wide + (wide + 1) * experts
        + 3 * int(net["experts_held"]) * d * int(net["expert_width"]),
    }
    trunk = sum(each[kind] for kind in net["pattern"]) + max(n_e - 1, 0) * wide
    return obs_width * d + d + d * d + d + trunk + d + (d + 1) * (actions + heads_out)


def _shares(config):
    """The share of the forward steps, and of the burn-in steps, that carry a token."""
    shape = config["shapes"]
    forward = float(shape.get("observed_share", 1.0))
    return forward, float(shape.get("observed_share_burn_in", forward))


def _tokens(config, cell):
    """(trained, burn-in) tokens an update, and the keys a token sees."""
    net, train = _net(config), cell["train_args"]
    rows = int(train["batch_size"]) * int(config["shapes"]["players"])
    forward, burn = _shares(config)
    in_a_row = int(train["burn_in_steps"]) * burn + int(train["forward_steps"]) * forward
    return (rows * int(train["forward_steps"]) * forward,
            rows * int(train["burn_in_steps"]) * burn,
            min(int(net["memory_len"]), (in_a_row + 1) / 2))


def _compute_bytes(config):
    return 2 if config.get("train_args", {}).get("compute_dtype") == "bfloat16" else 4


def train_update(config, cell):
    net, shape = _net(config), config["shapes"]
    d = int(net["d_model"])
    trained, burn, keys = _tokens(config, cell)
    obs, actions, scalars = (int(shape[k]) for k in ("observation_width", "actions", "scalar_heads"))
    per_token = obs * d + d * d + d * (actions + scalars) + sum(
        layer_macs_per_token(net, kind, keys) for kind in net["pattern"])
    n_params = parameters(net, obs, actions, scalars)
    compute_bytes = _compute_bytes(config)
    state = n_params * (2 * compute_bytes + 2 * 4 + 3 * 4 * 2)
    # a sub-layer's saved activations, in d_model-wide rows a token: its input
    # and norm, and the mixer's products (C: q, k, v before and after the
    # mixing, the attention's result, 0.625 d each way; E: the router's 256s
    # and, for the share of tokens on a held expert, the 2 d gate-and-up and
    # the d product)
    saved = {"C": 2.0 + 2.5, "E": 2.0 + 0.5 + 3.0 * routed_rows_per_token(net)
             * int(net["expert_width"]) / d}
    activations = (trained + burn) * sum(saved[k] for k in net["pattern"]) * d * compute_bytes * 2
    return {"flops": float(2 * per_token * (3 * trained + burn)),
            "bytes": float(state + activations),
            "tokens": trained + burn, "parameters": n_params}


def scope_work(config, cell):
    net = _net(config)
    d, q, kv, head, latent = _sizes(net)
    width = int(net["expert_width"])
    trained, burn, _ = _tokens(config, cell)
    passes = 3 * trained + burn
    compute_bytes = _compute_bytes(config)
    n_c, n_e = net["pattern"].count("C"), net["pattern"].count("E")
    rows = n_e * routed_rows_per_token(net) * (trained + burn)
    return {
        # a routed row read at d and written at 2 x width, read at width and
        # written at d, forward and backward; the held experts' weights read
        # forward and backward and their gradient written
        "experts": {
            "flops": float(2 * 3 * d * width * 3 * rows),
            "bytes": float(3 * rows * 2 * (d + 1.5 * width) * compute_bytes
                           + n_e * 3 * int(net["experts_held"]) * 3 * d * width * compute_bytes),
            "rows": float(rows),
        },
        # [q~; k~] and the two value halves read, q, k and v written, forward
        # and backward; the convolutions' weights read twice, their gradient
        # written
        "cca_mix": {
            "flops": float(2 * n_c * cca_mix_macs_per_token(net) * passes),
            "bytes": float(n_c * (passes * 2 * (latent + kv * head)
                                  + 3 * (int(net["cca_time1"]) * latent * head
                                         + int(net["cca_time0"]) * latent)) * compute_bytes),
        },
    }
