"""Operations and bytes one step of the acting path needs for a
``granitemoehybrid`` period (Mamba-2 or attention, then routed SwiGLU experts
with a shared one, in every layer) as a policy trunk, from shapes.

``act_step(config, cell)`` counts one step of the streaming rollout (one
iteration of its scan): every lane plays one game step, and with
``observation: false`` exactly one player of a lane observes, so a step
carries ``lanes`` tokens.  The rollout applies the net to every (lane,
player) row; the rows of players who do not observe are work it does, not
work the step needs, and no term counts them.

Per token, multiply-adds: the two encoder layers and the heads; by kind of
sub-layer

* ``M``: ``in_proj`` and ``out_proj``, the depthwise conv, and the
  recurrence itself: the state's decay-and-add and its read, one
  multiply-add each an element of the (heads x head_dim x state) state.
* ``E``: the router over all experts, the shared expert's three matrices
  (the fused input matrix is two), and the routed rows that fall on held
  experts, ``top_k x experts_held / n_experts`` of a row a token (a uniform
  router's share), three matrices each.
* ``*``: q, k, v, o, and the scores and the mix over
  ``shapes.attended_keys_mean`` keys.

2 FLOP a multiply-add, forward only.  Not counted: norms, gates, softmaxes,
sorting and gathers, the environment, sampling.

Bytes, the least HBM traffic of a step: every held parameter read once in
the type it is held in, and the per-row state (SSM state, conv tail, key and
value ring, float32) of the rows that observe read once and written once.
This is what bounds the step: at tens of rows every product is a matrix read.

``scope_work`` gives the same counts inside the scopes a profile shows.
"""

BYTES = {"bfloat16": 2, "float32": 4}


def _net(config):
    return config["env_args"]["net_args"]


def _mamba_sizes(net):
    heads, width = int(net["mamba_heads"]), int(net["mamba_head_dim"])
    groups, state = int(net["n_groups"]), int(net["state_size"])
    inner = heads * width
    return heads, width, groups, state, inner, inner + 2 * groups * state


def routed_rows_per_token(net):
    return int(net["top_k"]) * int(net["experts_held"]) / int(net["n_experts"])


def _fused(net):
    return 3 if net.get("gated_experts") else 2     # matrices of d x width an expert


def sublayer_parameters(net, kind):
    d = int(net["d_model"])
    heads, _, _, _, inner, conv_dim = _mamba_sizes(net)
    if kind == "M":
        return (d + d * (inner + conv_dim + heads) + (int(net["conv_kernel"]) + 1) * conv_dim
                + 3 * heads + inner + inner * d)
    if kind == "E":
        bias = int(net["n_experts"]) if net.get("router", "sigmoid") == "sigmoid" else 0
        return (d + d * int(net["n_experts"]) + bias + _fused(net) * d * (
            int(net["shared_width"]) + int(net["experts_held"]) * int(net["expert_width"])))
    return d + 2 * d * int(net["head_dim"]) * (int(net["n_heads"]) + int(net["n_kv_heads"]))


def parameters(config):
    net, shape = _net(config), config["shapes"]
    d = int(net["d_model"])
    obs, actions, scalars = (int(shape[k]) for k in ("observation_width", "actions", "scalar_heads"))
    trunk = sum(sublayer_parameters(net, kind) for kind in net["pattern"])
    return obs * d + d + d * d + d + trunk + d + (d + 1) * (actions + scalars)


def state_bytes_per_row(net):
    """The hidden tree of one (lane, player) row, float32 in every leaf."""
    heads, width, _, state, _, conv_dim = _mamba_sizes(net)
    mamba = heads * width * state + (int(net["conv_kernel"]) - 1) * conv_dim
    ring = 2 * int(net["memory_len"]) * int(net["n_kv_heads"]) * int(net["head_dim"])
    pattern = net["pattern"] * int(net.get("loops", 1))
    return 4 * (pattern.count("M") * mamba + pattern.count("*") * ring)


def sublayer_macs_per_token(net, kind, keys):
    d = int(net["d_model"])
    if kind == "M":
        heads, width, _, state, inner, conv_dim = _mamba_sizes(net)
        return (d * (inner + conv_dim + heads) + inner * d
                + int(net["conv_kernel"]) * conv_dim + 2 * heads * width * state)
    if kind == "E":
        return (d * int(net["n_experts"]) + _fused(net) * d * int(net["shared_width"])
                + routed_rows_per_token(net) * _fused(net) * d * int(net["expert_width"]))
    q, kv, width = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    return 2 * d * width * (q + kv) + 2 * keys * q * width


def act_step(config, cell):
    """One step of the rollout's scan: ``flops`` and ``bytes`` it needs,
    the ``tokens`` it carries, and what the bytes are made of."""
    net, shape = _net(config), config["shapes"]
    d = int(net["d_model"])
    lanes = int(cell["train_args"]["device_rollout_games"])
    obs, actions, scalars = (int(shape[k]) for k in ("observation_width", "actions", "scalar_heads"))
    keys = float(shape["attended_keys_mean"])
    per_token = obs * d + d * d + d * (actions + scalars) + sum(
        sublayer_macs_per_token(net, kind, keys) for kind in net["pattern"])
    weights = parameters(config) * BYTES[net.get("param_dtype", "float32")]
    state = 2 * lanes * state_bytes_per_row(net)
    return {"flops": float(2 * per_token * lanes), "bytes": float(weights + state),
            "tokens": float(lanes), "weight_bytes": float(weights), "state_bytes": float(state),
            "parameters": parameters(config)}


def scope_work(config, cell):
    """scope -> ``flops`` and ``bytes`` a step needs under it: the routed
    experts' products (``experts``, with the ``rows`` the count stands for),
    the shared expert, the router, the recurrence (``ssd``: the observing
    rows' SSM state read and written once)."""
    net = _net(config)
    d, width = int(net["d_model"]), int(net["expert_width"])
    heads, head_width, _, state, _, _ = _mamba_sizes(net)
    lanes = int(cell["train_args"]["device_rollout_games"])
    size = BYTES[net.get("param_dtype", "float32")]
    n_m, n_e = net["pattern"].count("M"), net["pattern"].count("E")
    rows = n_e * routed_rows_per_token(net) * lanes
    fused = _fused(net)
    return {
        "experts": {
            "flops": float(2 * fused * d * width * rows),
            "bytes": float(n_e * int(net["experts_held"]) * fused * d * width * size),
            "rows": float(rows),
        },
        "shared_expert": {
            "flops": float(2 * n_e * fused * d * int(net["shared_width"]) * lanes),
            "bytes": float(n_e * fused * d * int(net["shared_width"]) * size),
        },
        "route": {
            "flops": float(2 * n_e * d * int(net["n_experts"]) * lanes),
            "bytes": float(n_e * d * int(net["n_experts"]) * size),
        },
        "ssd": {
            "flops": float(2 * n_m * 2 * heads * head_width * state * lanes),
            "bytes": float(n_m * 2 * lanes * heads * head_width * state * 4),
        },
    }
