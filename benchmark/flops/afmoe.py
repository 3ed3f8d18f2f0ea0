"""Operations and bytes one update of an ``afmoe`` trunk (gated grouped-query
attention sub-layers with per-head q/k norms, local ``W`` and global ``*``; a
dense SwiGLU, ``-``; sigmoid-routed SwiGLU expert sub-layers with a shared
expert, ``E``; a norm before and after every sub-layer) needs as a policy
trunk, from shapes.

Counted per token: the multiply-adds of the two encoder layers, of every
sub-layer's products by its kind, and of the heads.  A token is a step of a
player's window that carries an observation: ``shapes.observed_share`` of
the forward steps and ``shapes.observed_share_burn_in`` of the burn-in steps
(the traffic is ``nemotron_h.py``'s, ``ouro.py``'s, ``zaya.py``'s and
``kanana.py``'s).  The program runs its dense products over the packed
array's padding and its experts over every slot of a row buffer; that is work
it does, not work the update needs, so no term counts padding, a checkpoint's
replay or an empty buffer slot.

* ``W`` and ``*``: the five projections (q, k, v, the gate's, o), and the
  scores and the mix against the keys a token sees: causal over its row's
  tokens, so (tokens + 1) / 2 on average, at most the layer's reach
  (``min(window, memory_len)`` steps in a ``W`` layer, ``memory_len`` in a
  ``*`` one).  A row of this traffic holds at most 92 + 8 tokens, so at the
  published window of 2,048 neither bound cuts a key: a local and a global
  layer cost the same here, and the window is slack in every term.
* ``-``: gate, up and down.
* ``E``: the router over all experts, the shared expert's three products, and
  the routed rows that fall on held experts: ``top_k x experts_held /
  n_experts`` of a row a token (a uniform router's share), three products
  each (the fused gate-and-up, and down).

2 FLOP a multiply-add; a trained token costs forward once and backward
twice, a burn-in token forward only.  Not counted: norms, rotations, softmax,
sigmoids, sorting, gathers, the loss, the optimizer.

Bytes: the least HBM traffic: parameters read twice in the compute type,
gradients written and read once in float32, parameters and Adam's two
moments read and written once in float32, and each sub-layer's saved
activations written and read once in the compute type.

``scope_work`` gives the same counts inside the scopes ``experts`` (the
routed rows' grouped products, with ``rows``: the routed rows an update the
count stands for, so that a reader holding ``counter_rows_held`` can rescale),
``attn_proj`` (the five projections of every attention sub-layer) and ``mlp``
(the three products of every dense ``-`` sub-layer).
"""


def _net(config):
    return config["env_args"]["net_args"]


def _sizes(net):
    """d, query heads, key heads, a head's width."""
    return tuple(int(net[k]) for k in ("d_model", "n_heads", "n_kv_heads", "head_dim"))


def attn_proj_macs_per_token(net):
    d, heads, kv_heads, dim = _sizes(net)
    gate = 1 if net.get("attn_gate") else 0
    return d * dim * ((2 + gate) * heads + 2 * kv_heads)


def attn_core_macs_per_token(net, keys):
    _, heads, _, dim = _sizes(net)
    return keys * heads * 2 * dim


def reach(net, kind):
    """The observed steps back a query of a ``kind`` layer sees."""
    memory = int(net["memory_len"])
    return min(int(net["window"]), memory) if kind == "W" else memory


def routed_rows_per_token(net):
    return int(net["top_k"]) * int(net["experts_held"]) / int(net["n_experts"])


def layer_macs_per_token(net, kind, in_a_row):
    d = int(net["d_model"])
    if kind in "W*":
        keys = min(reach(net, kind), (in_a_row + 1) / 2)
        return attn_proj_macs_per_token(net) + attn_core_macs_per_token(net, keys)
    if kind == "-":
        return 3 * d * int(net["mlp_width"])
    if kind == "E":
        return (d * int(net["n_experts"]) + 3 * d * int(net["shared_width"])
                + routed_rows_per_token(net) * 3 * d * int(net["expert_width"]))
    raise ValueError(f"flops/afmoe.py counts 'W', '*', '-' and 'E' layers, not {kind!r}")


def parameters(net, obs_width, actions, heads_out):
    d, _, _, dim = _sizes(net)
    experts = int(net["n_experts"])
    norms = 2 * d if net.get("sandwich") else d
    attention = norms + attn_proj_macs_per_token(net) + (2 * dim if net.get("qk_norm") else 0)
    each = {
        "W": attention, "*": attention,
        "-": norms + 3 * d * int(net["mlp_width"]),
        "E": norms + (d + 1) * experts + 3 * d * (
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
    """(trained, burn-in) tokens an update, and the tokens a row holds."""
    train = cell["train_args"]
    rows = int(train["batch_size"]) * int(config["shapes"]["players"])
    forward, burn = _shares(config)
    in_a_row = int(train["burn_in_steps"]) * burn + int(train["forward_steps"]) * forward
    return (rows * int(train["forward_steps"]) * forward,
            rows * int(train["burn_in_steps"]) * burn, in_a_row)


def _compute_bytes(config):
    return 2 if config.get("train_args", {}).get("compute_dtype") == "bfloat16" else 4


def _saved(net):
    """A sub-layer's saved activations, in d_model-wide rows a token: its
    input, its norm and the branch its second norm reads, and its products
    (W, *: q, k, v, the gate, the mix and the gated mix; -: gate, up and their
    product; E: the scores, the shared expert's fused product and gated half,
    and for the share of tokens on a held expert the same of an expert)."""
    d, heads, kv_heads, dim = _sizes(net)
    attention = 3.0 + dim * (4 * heads + 2 * kv_heads) / d
    return {
        "W": attention, "*": attention,
        "-": 3.0 + 3.0 * int(net["mlp_width"]) / d,
        "E": 3.0 + (int(net["n_experts"]) + 3.0 * int(net["shared_width"])
                    + 3.0 * routed_rows_per_token(net) * int(net["expert_width"])) / d,
    }


def train_update(config, cell):
    net, shape = _net(config), config["shapes"]
    d = int(net["d_model"])
    trained, burn, in_a_row = _tokens(config, cell)
    obs, actions, scalars = (int(shape[k]) for k in ("observation_width", "actions", "scalar_heads"))
    per_token = obs * d + d * d + d * (actions + scalars) + sum(
        layer_macs_per_token(net, kind, in_a_row) for kind in net["pattern"])
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
    d, heads, kv_heads, dim = _sizes(net)
    width = int(net["expert_width"])
    trained, burn, _ = _tokens(config, cell)
    passes = 3 * trained + burn
    compute_bytes = _compute_bytes(config)
    n_a = sum(net["pattern"].count(kind) for kind in "W*")
    n_e, n_d = net["pattern"].count("E"), net["pattern"].count("-")
    mlp = int(net["mlp_width"])
    rows = n_e * routed_rows_per_token(net) * (trained + burn)
    proj = attn_proj_macs_per_token(net)
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
        # a pass reads the normed input and the gated mix and writes q, k, v,
        # the gate and the result; the five matrices read forward and
        # backward, their gradient written
        "attn_proj": {
            "flops": float(2 * n_a * proj * passes),
            "bytes": float(n_a * (passes * (2 * d + dim * (3 * heads + 2 * kv_heads))
                                  + 3 * proj) * compute_bytes),
        },
        # a token's row read and written at d, gate, up and their product
        # written and read back at mlp_width; the three matrices read forward
        # and backward, their gradient written
        "mlp": {
            "flops": float(2 * n_d * 3 * d * mlp * passes),
            "bytes": float(n_d * (passes * (2 * d + 3 * mlp) + 3 * 3 * d * mlp) * compute_bytes),
        },
    }
