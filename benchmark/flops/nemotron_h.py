"""Operations and bytes one update of a ``nemotron_h`` tower (Mamba-2
mixers, routed experts with a shared one, grouped-query attention) needs as
a policy trunk, from shapes.

Counted per token: the multiply-adds of the two encoder layers, of every
layer's products by its kind, and of the heads.  A token is a step of a
player's window that carries an observation: ``shapes.observed_share`` of
the forward steps and ``shapes.observed_share_burn_in`` of the burn-in steps
(1 where the configuration gives none).  The program runs its dense products
over the padded steps too; that is work it does, not work the update needs,
so no term counts it.

* ``M``: ``in_proj`` and ``out_proj``; the depthwise conv; the scan in its
  chunked form at the configuration's ``chunk``: inside a chunk the causal
  half of ``C B^T`` per group and of the decay-weighted product with ``x``
  per head (a step sees on average (chunk + 1) / 2 steps of its chunk), the
  state a chunk hands on (``x B^T``) and its read (``C S``).
* ``E``: the router over all experts, the shared expert, and the routed
  rows that fall on held experts: ``top_k x experts_held / n_experts`` of a
  row a token (a uniform router's share), two products each.
* ``*``: q, k, v, o and the scores and the mix over the keys the window
  allows: causal over a row's tokens, so (tokens + 1) / 2 on average, at
  most ``memory_len``.

2 FLOP a multiply-add; a trained token costs forward once and backward
twice, a burn-in token forward only.  Not counted: norms, gates, softmax,
sorting, gathers, the loss, the optimizer, and what a checkpoint replays.

Bytes: the least HBM traffic, as there: parameters read twice in the compute
type, gradients written and read once in float32, parameters and Adam's two
moments read and written once in float32, and each layer's saved activations
written and read once in the compute type.

``scope_work`` gives the same counts inside the scopes ``ssd`` (the scan
alone, without its projections) and ``experts`` (the routed rows' two grouped
products), with ``rows``: the routed rows an update the count stands for, so
that a reader holding the run's own count can rescale.
"""


def _net(config):
    return config["env_args"]["net_args"]


def _mamba_sizes(net):
    heads, width = int(net["mamba_heads"]), int(net["mamba_head_dim"])
    groups, state = int(net["n_groups"]), int(net["state_size"])
    inner = heads * width
    return heads, width, groups, state, inner, inner + 2 * groups * state


def ssd_macs_per_token(net):
    """The chunked scan alone."""
    heads, width, groups, state, _, _ = _mamba_sizes(net)
    seen = (int(net["chunk"]) + 1) / 2          # steps of its chunk a step looks back on
    inside = seen * (groups * state + heads * width)
    across = 2 * heads * width * state          # the state handed on, and its read
    return inside + across


def layer_macs_per_token(net, kind, keys):
    d = int(net["d_model"])
    if kind == "M":
        heads, _, _, _, inner, conv_dim = _mamba_sizes(net)
        return (d * (inner + conv_dim + heads) + inner * d
                + int(net["conv_kernel"]) * conv_dim + ssd_macs_per_token(net))
    if kind == "E":
        return (d * int(net["n_experts"]) + 2 * d * int(net["shared_width"])
                + routed_rows_per_token(net) * 2 * d * int(net["expert_width"]))
    q, kv, width = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    return 2 * d * width * (q + kv) + 2 * keys * q * width


def routed_rows_per_token(net):
    return int(net["top_k"]) * int(net["experts_held"]) / int(net["n_experts"])


def parameters(net, obs_width, actions, heads_out):
    d = int(net["d_model"])
    heads, _, _, _, inner, conv_dim = _mamba_sizes(net)
    each = {
        "M": d + d * (inner + conv_dim + heads) + (int(net["conv_kernel"]) + 1) * conv_dim
        + 3 * heads + inner + inner * d,
        "E": d + d * int(net["n_experts"]) + int(net["n_experts"])
        + 2 * d * int(net["shared_width"])
        + 2 * int(net["experts_held"]) * d * int(net["expert_width"]),
        "*": d + 2 * d * int(net["head_dim"]) * (int(net["n_heads"]) + int(net["n_kv_heads"])),
    }
    trunk = sum(each[kind] for kind in net["pattern"])
    return obs_width * d + d + d * d + d + trunk + d + (d + 1) * (actions + heads_out)


def _shares(config):
    """The share of the forward steps, and of the burn-in steps, that carry a token."""
    shape = config["shapes"]
    forward = float(shape.get("observed_share", 1.0))
    return forward, float(shape.get("observed_share_burn_in", forward))


def _tokens(config, cell):
    """(trained, burn-in) tokens an update."""
    train = cell["train_args"]
    rows = int(train["batch_size"]) * int(config["shapes"]["players"])
    forward, burn = _shares(config)
    return (rows * int(train["forward_steps"]) * forward,
            rows * int(train["burn_in_steps"]) * burn)


def train_update(config, cell):
    net, shape, train = _net(config), config["shapes"], cell["train_args"]
    d = int(net["d_model"])
    forward_share, burn_share = _shares(config)
    in_a_row = (int(train["burn_in_steps"]) * burn_share
                + int(train["forward_steps"]) * forward_share)      # a row's tokens
    keys = min(int(net["memory_len"]), (in_a_row + 1) / 2)
    obs, actions, scalars = (int(shape[k]) for k in ("observation_width", "actions", "scalar_heads"))
    per_token = obs * d + d * d + d * (actions + scalars) + sum(
        layer_macs_per_token(net, kind, keys) for kind in net["pattern"])
    trained, burn = _tokens(config, cell)
    n_params = parameters(net, obs, actions, scalars)
    compute_bytes = 2 if config.get("train_args", {}).get("compute_dtype") == "bfloat16" else 4
    state = n_params * (2 * compute_bytes + 2 * 4 + 3 * 4 * 2)
    # a layer's saved activations, in d_model-wide rows a token: its input and
    # norm, and the mixer's products (M: in_proj's 3.8 d and the gated 1.5 d;
    # E: the shared expert's 1.4 d; *: q, k, v, the mix)
    saved = {"M": 8.0, "E": 4.0, "*": 6.0}
    activations = (trained + burn) * sum(saved[k] for k in net["pattern"]) * d * compute_bytes * 2
    return {"flops": float(2 * per_token * (3 * trained + burn)),
            "bytes": float(state + activations),
            "tokens": trained + burn, "parameters": n_params}


def scope_work(config, cell):
    net = _net(config)
    d, width = int(net["d_model"]), int(net["expert_width"])
    heads, head_width, groups, state, inner, _ = _mamba_sizes(net)
    trained, burn = _tokens(config, cell)
    passes = 3 * trained + burn
    compute_bytes = 2 if config.get("train_args", {}).get("compute_dtype") == "bfloat16" else 4
    n_m, n_e = net["pattern"].count("M"), net["pattern"].count("E")
    rows = n_e * routed_rows_per_token(net) * (trained + burn)
    return {
        # x, B, C, dt read and y written, forward and backward, and nothing
        # of the chunk x chunk matrices (a kernel would keep them on chip)
        "ssd": {
            "flops": float(2 * n_m * ssd_macs_per_token(net) * passes),
            "bytes": float(n_m * passes * (2 * inner + 2 * groups * state + heads) * compute_bytes),
        },
        # a routed row read and written at both widths, the held experts'
        # weights read once a pass and their gradient written
        "experts": {
            "flops": float(2 * 2 * d * width * 3 * rows),
            "bytes": float(3 * rows * 2 * (d + width) * compute_bytes
                           + n_e * 4 * int(net["experts_held"]) * 2 * d * width * compute_bytes),
            "rows": float(rows),
        },
    }
