"""A routed layer in plain ``jax.numpy`` (sigmoid scores, top-k of a few
experts, gates renormalised over the chosen), as a *system* that mixes the
experts through a one-hot mask and as a *reference* that gathers the chosen, for
``harness.judge_forward``'s routed contract: test_reference.py injects the
faults at four experts, and

    python benchmark/tests/routed_toy.py [--seeds 3]

runs the system in bf16 on whatever device jax has (the TPU, through the
builders' chip tool) at a size where rounding does flip choices, and prints
what the three comparisons read.  Nothing of the program is imported.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

LAYER = "layer0"


def make(seed, tokens=(2, 6, 2), width=8, experts=4, heads=5):
    """(params, batch) from ``seed``: rows (B, T, P, width) of unit-RMS
    tokens, a router, ``experts`` dense experts and a head."""
    b, t, p = tokens
    kx, kr, ke, kh = jax.random.split(jax.random.PRNGKey(seed), 4)
    params = {
        "router": jax.random.normal(kr, (width, experts)) / jnp.sqrt(width),
        "experts": jax.random.normal(ke, (experts, width, width)) / jnp.sqrt(width),
        "head": jax.random.normal(kh, (width, heads)) / jnp.sqrt(width),
    }
    return params, {"x": jax.random.normal(kx, (b, t, p, width))}


def _scores(params, x):
    return jax.nn.sigmoid(x @ params["router"])


def _top(ranked, k):
    return jnp.argsort(-ranked, axis=-1)[..., :k].astype(jnp.int32)


def _gates(scores, top):
    gate = jnp.take_along_axis(scores, top, axis=-1)
    return gate / gate.sum(axis=-1, keepdims=True)


def reference_rows(params, batch, config, burn_in, choices=None):
    """The plain reference: float32, the chosen experts gathered.  Told the
    ``choices``, it takes them for its own top-k and still computes the
    gates from its own scores at those indices."""
    x = batch["x"][:, burn_in:].astype(jnp.float32)
    scores = _scores(params, x)
    top = _top(scores, int(config["top_k"])) if choices is None else choices[LAYER]
    every = jnp.einsum("...d,kde->...ke", x, params["experts"])
    chosen = jnp.take_along_axis(every, top[..., None], axis=-2)      # (..., k, width)
    mixed = (chosen * _gates(scores, top)[..., None]).sum(axis=-2)
    return {"policy": (x + mixed) @ params["head"], "choices": {LAYER: top}}


def system_rows(params, batch, config, burn_in, dtype=jnp.float32, select=lambda s: s):
    """The system: the experts mixed through a one-hot mask of the chosen
    (no gather), in ``dtype``.  ``select`` maps the scores to
    what the top-k is taken from (the identity, or a fault)."""
    params = jax.tree.map(lambda w: w.astype(dtype), params)
    x = batch["x"][:, burn_in:].astype(dtype)
    scores = _scores(params, x)
    top = _top(select(scores), int(config["top_k"]))
    mask = jax.nn.one_hot(top, scores.shape[-1], dtype=dtype).sum(axis=-2)
    gates = mask * scores / (mask * scores).sum(axis=-1, keepdims=True)
    every = jnp.einsum("...d,kde->...ke", x, params["experts"])
    mixed = (every * gates[..., None]).sum(axis=-2)
    return {"policy": ((x + mixed) @ params["head"]).astype(jnp.float32),
            "choices": {LAYER: top}}


def main(argv=None) -> int:
    from benchmark import harness

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=3)
    opts = parser.parse_args(argv)
    config = {"name": "routed_toy", "top_k": 6, "reference_tolerance": 0.03,
              "reference_tolerance_f32": 1e-4, "choices_agreement_floor": 0.9}
    device = jax.devices()[0]
    for seed in range(opts.seeds):
        # 4,096 tokens of width 512 over 16 experts: bf16 rounds the scores
        # by more than many a token's 6th and 7th lie apart
        params, batch = make(seed, tokens=(8, 256, 2), width=512, experts=16, heads=64)
        checks, notes, compared = harness.judge_forward(
            lambda p, b: system_rows(p, b, config, 0, jnp.bfloat16),
            reference_rows, params, batch, config, 0,
            system_f32=lambda p, b: system_rows(p, b, config, 0))
        print(json.dumps({
            "seed": seed, "device": device.device_kind, "platform": device.platform,
            "checks": checks, "compared": compared,
            "free_at_the_forced_tolerance": notes["reference_free_max_abs_diff"],
        }, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
