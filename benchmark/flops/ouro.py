"""Operations and bytes one update of a looped dense transformer (``ouro``:
sandwiched layers of attention then a gated MLP, the stack run ``loops``
times over its own weights) needs as a policy trunk, from shapes.

Counted per token and per *application*: a layer's products are made once a
pass, so its multiply-adds count ``loops`` times while its parameters count
once (6 x parameters x tokens would be ``loops`` times too low).  A token is
a step of a player's window that carries an observation:
``shapes.observed_share`` of the forward steps and
``shapes.observed_share_burn_in`` of the burn-in steps, as in
``nemotron_h.py`` (the traffic is the same).  The program runs its dense
products over the packed array's padding too; that is work it does, not work
the update needs, so no term counts it.

* ``*``: q, k, v, o and the scores and the mix over the keys a token sees:
  causal over its row's tokens, so (tokens + 1) / 2 on average.
* ``-``: the gate, up and down products.
* once, not per pass: the two encoder layers and the heads.

2 FLOP a multiply-add; a trained token costs forward once and backward
twice, a burn-in token forward only.  Not counted: norms, rotations, the
softmax, silu and the gating product, the exit gate (a product of width 1),
the loss, the optimizer, and what a checkpoint replays.

Bytes: the least HBM traffic: the stack's parameters read twice a pass in
the compute type (a layer's 51M do not stay on the chip from one pass to the
next), the others twice; gradients written and read once in float32,
parameters and Adam's two moments read and written once in float32 (once an
update, whatever ``loops``: a quarter of an unlooped model's optimizer
traffic per FLOP at ``loops`` 4), and each application's saved activations
written and read once in the compute type.

``scope_work`` gives the same counts inside the scopes ``mlp`` (the three
products of every ``-`` application) and ``attn`` (the four projections, the
scores and the mix of every ``*`` application).
"""


def _net(config):
    return config["env_args"]["net_args"]


def _sizes(net):
    d, width = int(net["d_model"]), int(net["mlp_width"])
    q, kv, head = int(net["n_heads"]), int(net["n_kv_heads"]), int(net["head_dim"])
    return d, width, q, kv, head


def layer_macs_per_token(net, kind, keys):
    """One application of one sub-layer."""
    d, width, q, kv, head = _sizes(net)
    if kind == "-":
        return 3 * d * width
    if kind == "*":
        return 2 * d * head * (q + kv) + 2 * keys * q * head
    raise ValueError(f"flops/ouro.py counts '*' and '-' layers, not {kind!r}")


def parameters(net, obs_width, actions, heads_out):
    d, width, q, kv, head = _sizes(net)
    norms = 2 * d if net.get("sandwich") else d
    each = {"*": norms + 2 * d * head * (q + kv), "-": norms + 3 * d * width}
    trunk = sum(each[kind] for kind in net["pattern"])
    gate = d + 1 if int(net.get("loops", 1)) > 1 else 0
    return (obs_width * d + d + d * d + d + trunk + d + gate + (d + 1) * (actions + heads_out),
            trunk)


def _shares(config):
    """The share of the forward steps, and of the burn-in steps, that carry a token."""
    shape = config["shapes"]
    forward = float(shape.get("observed_share", 1.0))
    return forward, float(shape.get("observed_share_burn_in", forward))


def _tokens(config, cell):
    """(trained, burn-in) tokens an update, and the keys a token sees."""
    train = cell["train_args"]
    rows = int(train["batch_size"]) * int(config["shapes"]["players"])
    forward, burn = _shares(config)
    in_a_row = int(train["burn_in_steps"]) * burn + int(train["forward_steps"]) * forward
    return (rows * int(train["forward_steps"]) * forward,
            rows * int(train["burn_in_steps"]) * burn, (in_a_row + 1) / 2)


def _compute_bytes(config):
    return 2 if config.get("train_args", {}).get("compute_dtype") == "bfloat16" else 4


def train_update(config, cell):
    net, shape = _net(config), config["shapes"]
    d, loops = int(net["d_model"]), int(net.get("loops", 1))
    trained, burn, keys = _tokens(config, cell)
    obs, actions, scalars = (int(shape[k]) for k in ("observation_width", "actions", "scalar_heads"))
    per_token = obs * d + d * d + d * (actions + scalars) + loops * sum(
        layer_macs_per_token(net, kind, keys) for kind in net["pattern"])
    n_params, trunk = parameters(net, obs, actions, scalars)
    compute_bytes = _compute_bytes(config)
    state = ((n_params - trunk) * 2 + trunk * 2 * loops) * compute_bytes \
        + n_params * (2 * 4 + 3 * 4 * 2)
    # an application's saved activations, in d_model-wide rows a token: its
    # input, norm and output, and the mixer's products (*: q, k, v, the mix;
    # -: gate, up and their product, each mlp_width / d_model wide)
    saved = {"*": 7.0, "-": 3.0 + 3.0 * int(net["mlp_width"]) / d}
    activations = (trained + burn) * loops * sum(saved[k] for k in net["pattern"]) \
        * d * compute_bytes * 2
    return {"flops": float(2 * per_token * (3 * trained + burn)),
            "bytes": float(state + activations),
            "tokens": trained + burn, "parameters": n_params,
            "applications": loops * len(net["pattern"])}


def scope_work(config, cell):
    net = _net(config)
    d, width, q, kv, head = _sizes(net)
    loops = int(net.get("loops", 1))
    trained, burn, keys = _tokens(config, cell)
    passes = 3 * trained + burn
    compute_bytes = _compute_bytes(config)
    work = {}
    for scope, kind, weights, rows in (
            # a token's row read and written at d_model, gate, up and their
            # product written and read back at mlp_width
            ("mlp", "-", 3 * d * width, 2 * d + 3 * width),
            # a token's row in and out, q, k, v and the mix between them
            ("attn", "*", 2 * d * head * (q + kv), 2 * d + 2 * head * (q + kv))):
        applications = loops * net["pattern"].count(kind)
        work[scope] = {
            "flops": float(2 * applications * layer_macs_per_token(net, kind, keys) * passes),
            # the weights read forward and backward and their gradient written,
            # every application
            "bytes": float(applications * (3 * weights + passes * rows) * compute_bytes),
        }
    return work
