"""Operations and bytes one update of the memory transformer needs, from
shapes.

Counted per token (one player's observation at one step): the
multiply-adds of the two encoder layers, of every block's q, k, v, o and
MLP products, of attention over the keys the window allows (at most
``memory_len``, whatever the implementation computes: the einsum path
scores all T keys and masks, and that extra work is not required work),
and of the heads.  2 FLOP a multiply-add; a trained token costs forward
once and backward twice, a burn-in token forward only.  Not counted:
LayerNorm, softmax, ReLU, the loss, the optimizer.

Bytes: the least HBM traffic: parameters read twice in the compute type
(forward, backward), gradients written and read once in float32, the
parameters and Adam's two moments read and written once in float32, and
each block's saved activations (about twelve d_model-wide rows a token: two
norms, q, k, v, the attention output, the 4d MLP row, two residuals) written once
and read once in the compute type.
"""


def forward_macs_per_token(obs_width, d, layers, mlp_ratio, keys, actions, heads_out):
    encoder = obs_width * d + d * d
    block = 4 * d * d + 2 * mlp_ratio * d * d + 2 * keys * d
    return encoder + layers * block + d * (actions + heads_out)


def parameters(obs_width, d, layers, mlp_ratio, actions, heads_out):
    encoder = obs_width * d + d + d * d + d
    block = 4 * (d * d + d) + 2 * mlp_ratio * d * d + mlp_ratio * d + d + 4 * d
    return encoder + layers * block + 2 * d + (d + 1) * (actions + heads_out)


def train_update(config, cell):
    net = config["env_args"]["net_args"]
    train = cell["train_args"]
    shape = config["shapes"]
    d, layers = int(net["d_model"]), int(net["n_layers"])
    mlp_ratio = int(net.get("mlp_ratio", 4))
    t = int(train["burn_in_steps"]) + int(train["forward_steps"])
    keys = min(int(net["memory_len"]), t)
    rows = int(train["batch_size"]) * int(shape["players"])
    per_token = forward_macs_per_token(
        int(shape["observation_width"]), d, layers, mlp_ratio, keys,
        int(shape["actions"]), int(shape["scalar_heads"]))
    trained, burn = rows * int(train["forward_steps"]), rows * int(train["burn_in_steps"])
    flops = 2 * per_token * (3 * trained + burn)
    n_params = parameters(int(shape["observation_width"]), d, layers, mlp_ratio,
                          int(shape["actions"]), int(shape["scalar_heads"]))
    compute_bytes = 2 if config.get("train_args", {}).get("compute_dtype") == "bfloat16" else 4
    state = n_params * (2 * compute_bytes + 2 * 4 + 3 * 4 * 2)
    activations = (trained + burn) * layers * 12 * d * compute_bytes * 2
    return {"flops": float(flops), "bytes": float(state + activations),
            "tokens": trained + burn, "parameters": n_params}
