"""Plain reference of ``geesenet``: the HungryGeese GeeseNet forward pass
in straightforward float32 ``jax.numpy``: no flax, no ``lax.conv``, no
batching tricks.  Written from the architecture's description (upstream
HandyRL ``handyrl/envs/kaggle/hungry_geese.py`` ``GeeseNet``), with this
repo's two documented departures: GroupNorm(8) where upstream has
BatchNorm, and heads without bias.

    stem   torus conv 3x3 17->F, GroupNorm, ReLU
    tower  ``blocks`` x [ h = ReLU(h + GroupNorm(torus conv 3x3 F->F)) ]
    policy linear F->4 on the features at the own-head cell (obs plane 0)
    value  tanh(linear 2F->1 on [head-cell features, board-mean features])

A torus conv is written as nine rolls and nine matrix products, so that it
shares nothing with the convolution the system runs.  Callers set
``jax.default_matmul_precision("highest")``: a TPU otherwise runs float32
products as bf16 passes.
"""

import jax.numpy as jnp

EPS = 1e-6      # flax GroupNorm's default
GROUPS = 8


def torus_conv3x3(x, kernel):
    """x (N, H, W, C), kernel (3, 3, C, F); wrap-around padding."""
    out = 0.0
    for dy in range(3):
        for dx in range(3):
            shifted = jnp.roll(x, (1 - dy, 1 - dx), axis=(1, 2))
            out = out + jnp.einsum("nhwc,cf->nhwf", shifted, kernel[dy, dx])
    return out


def group_norm(x, scale, bias):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, GROUPS, c // GROUPS)
    mean = g.mean(axis=(1, 2, 4), keepdims=True)
    var = ((g - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + EPS)
    return g.reshape(n, h, w, c) * scale + bias


def conv_block(p, x):
    h = torus_conv3x3(x, p["Conv_0"]["kernel"])
    return group_norm(h, p["GroupNorm_0"]["scale"], p["GroupNorm_0"]["bias"])


def forward(params, obs, config=None):
    """obs (N, 17, 7, 11) float32 -> {'policy': (N, 4), 'value': (N, 1)}."""
    x = jnp.moveaxis(jnp.asarray(obs, jnp.float32), 1, -1)      # NHWC
    h = jnp.maximum(conv_block(params["ConvBlock_0"], x), 0.0)
    blocks = sum(1 for k in params if k.startswith("ConvBlock_")) - 1
    for i in range(1, blocks + 1):
        h = jnp.maximum(h + conv_block(params["ConvBlock_%d" % i], h), 0.0)
    head = (h * x[..., :1]).sum(axis=(1, 2))
    mean = h.mean(axis=(1, 2))
    policy = head @ params["Dense_0"]["kernel"]
    value = jnp.tanh(jnp.concatenate([head, mean], axis=-1) @ params["Dense_1"]["kernel"])
    return {"policy": policy, "value": value}
