"""Operations and bytes one update of a ``deepseek_v3`` trunk without a query
latent (multi-head latent attention sub-layers, ``L``; a dense SwiGLU, ``-``;
sigmoid-routed SwiGLU expert sub-layers with a shared expert, ``E``) needs as
a policy trunk, from shapes.

Counted per token: the multiply-adds of the two encoder layers, of every
sub-layer's products by its kind, and of the heads.  A token is a step of a
player's window that carries an observation: ``shapes.observed_share`` of
the forward steps and ``shapes.observed_share_burn_in`` of the burn-in steps
(the traffic is ``nemotron_h.py``'s, ``ouro.py``'s and ``zaya.py``'s).  The
program runs its dense products over the packed array's padding, its experts
over every slot of a row buffer, and expands the burn-in steps' latents a
second time in the forward part; that is work it does, not work the update
needs, so no term counts padding, a checkpoint's replay, an empty buffer slot
or a second expansion.

* ``L``: the four projections (q; the map to the latent and the shared key
  part; the map from the latent to every head's key part and value, once a
  token; o), and the scores (over ``qk_nope + qk_rope``) and the mix (over
  ``v_head``) against the keys a token sees: causal over its row's tokens, so
  (tokens + 1) / 2 on average, at most ``memory_len``.
* ``-``: gate, up and down.
* ``E``: the router over all experts, the shared expert's three products, and
  the routed rows that fall on held experts: ``top_k x experts_held /
  n_experts`` of a row a token (a uniform router's share), three products
  each (the fused gate-and-up, and down).

2 FLOP a multiply-add; a trained token costs forward once and backward
twice, a burn-in token forward only.  Not counted: norms, rotations, softmax,
sigmoid, sorting, gathers, the loss, the optimizer.

Bytes: the least HBM traffic: parameters read twice in the compute type,
gradients written and read once in float32, parameters and Adam's two
moments read and written once in float32, and each sub-layer's saved
activations written and read once in the compute type.

``scope_work`` gives the same counts inside the scopes ``experts`` (the
routed rows' grouped products, with ``rows``: the routed rows an update the
count stands for, so that a reader holding ``counter_rows_held`` can
rescale), ``mla_proj`` (the four projections) and ``mla_core`` (scores and
mix): the required work whatever form computes it, so that a kernel, or the
absorbed form in a window, is read against the same count.
"""


def _net(config):
    return config["env_args"]["net_args"]


def _sizes(net):
    """d, heads, a head's unrotated and rotated key parts and its value, the latent."""
    return tuple(int(net[k]) for k in (
        "d_model", "n_heads", "qk_nope_dim", "qk_rope_dim", "v_head_dim", "kv_latent"))


def mla_proj_macs_per_token(net):
    d, heads, nope, turned, wide, latent = _sizes(net)
    return (d * heads * (nope + turned) + d * (latent + turned)
            + latent * heads * (nope + wide) + heads * wide * d)


def mla_core_macs_per_token(net, keys):
    _, heads, nope, turned, wide, _ = _sizes(net)
    return keys * heads * (nope + turned + wide)


def routed_rows_per_token(net):
    return int(net["top_k"]) * int(net["experts_held"]) / int(net["n_experts"])


def layer_macs_per_token(net, kind, keys):
    d = int(net["d_model"])
    if kind == "L":
        return mla_proj_macs_per_token(net) + mla_core_macs_per_token(net, keys)
    if kind == "-":
        return 3 * d * int(net["mlp_width"])
    if kind == "E":
        return (d * int(net["n_experts"]) + 3 * d * int(net["shared_width"])
                + routed_rows_per_token(net) * 3 * d * int(net["expert_width"]))
    raise ValueError(f"flops/kanana.py counts 'L', '-' and 'E' layers, not {kind!r}")


def parameters(net, obs_width, actions, heads_out):
    d, heads, nope, turned, wide, latent = _sizes(net)
    experts = int(net["n_experts"])
    each = {
        "L": d + mla_proj_macs_per_token(net) + latent,
        "-": d + 3 * d * int(net["mlp_width"]),
        "E": d + (d + 1) * experts + 3 * d * (
            int(net["shared_width"]) + int(net["experts_held"]) * int(net["expert_width"])),
    }
    trunk = sum(each[kind] for kind in net["pattern"])
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


def _saved(net):
    """A sub-layer's saved activations, in d_model-wide rows a token: its
    input and norm, and its products (L: q, the latent with the shared key
    part, the heads' keys and values, the mix; -: gate, up and their product;
    E: the scores, the shared expert's fused product and gated half, and for
    the share of tokens on a held expert the same of an expert)."""
    d, heads, nope, turned, wide, latent = _sizes(net)
    return {
        "L": 2.0 + (heads * (2 * nope + turned + 2 * wide) + latent + turned) / d,
        "-": 2.0 + 3.0 * int(net["mlp_width"]) / d,
        "E": 2.0 + (int(net["n_experts"]) + 3.0 * int(net["shared_width"])
                    + 3.0 * routed_rows_per_token(net) * int(net["expert_width"])) / d,
    }


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
    saved = _saved(net)
    activations = (trained + burn) * sum(saved[k] for k in net["pattern"]) * d * compute_bytes * 2
    return {"flops": float(2 * per_token * (3 * trained + burn)),
            "bytes": float(state + activations),
            "tokens": trained + burn, "parameters": n_params}


def scope_work(config, cell):
    net = _net(config)
    d, heads, nope, turned, wide, latent = _sizes(net)
    width = int(net["expert_width"])
    trained, burn, keys = _tokens(config, cell)
    passes = 3 * trained + burn
    compute_bytes = _compute_bytes(config)
    n_l, n_e = net["pattern"].count("L"), net["pattern"].count("E")
    rows = n_e * routed_rows_per_token(net) * (trained + burn)
    return {
        # a routed row read at d and written at 2 x width, read at width and
        # written at d, forward and backward; the held experts' weights read
        # forward and backward and their gradient written
        "experts": {
            "flops": float(2 * 3 * d * width * n_e * routed_rows_per_token(net) * passes),
            "bytes": float(3 * rows * 2 * (d + 1.5 * width) * compute_bytes
                           + n_e * 3 * int(net["experts_held"]) * 3 * d * width * compute_bytes),
            "rows": float(rows),
        },
        # a pass reads the normed input and the mix and writes q, the latent
        # with the shared key part, the heads' keys and values, and the
        # result; the four matrices read forward and backward, their
        # gradient written
        "mla_proj": {
            "flops": float(2 * n_l * mla_proj_macs_per_token(net) * passes),
            "bytes": float(n_l * (passes * (2 * d + heads * (2 * nope + turned + 2 * wide)
                                            + latent + turned)
                                  + 3 * mla_proj_macs_per_token(net)) * compute_bytes),
        },
        # a pass reads a token's query, its heads' keys and values and the
        # shared key part, and writes the mix
        "mla_core": {
            "flops": float(2 * n_l * mla_core_macs_per_token(net, keys) * passes),
            "bytes": float(n_l * passes * (heads * (2 * nope + turned + 2 * wide) + turned)
                           * compute_bytes),
        },
    }
