"""On-chip shape sweep for the transformer MFU stage (bench.py 4d).

Times the REAL TrainContext step (Geister windows, UPGO-capable losses,
Adam) on the scaled TransformerNet across batch/window/dtype variants,
reusing one filled episode store, and prints one JSON line per variant:
updates/s, flops/update, MFU vs the chip's bf16 peak.  Used to pick the
shape the bench stage pins; run standalone on the chip:

    python tools/tune_transformer.py            # full sweep (~15 min)
    TUNE_T=6 python tools/tune_transformer.py   # shorter timed windows
    TUNE_ONLY=d1024_B64_T64_bf16,d1024_B64_T64_einsum \
        python tools/tune_transformer.py        # named variants only
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import bench  # noqa: E402  (repo root on path)

D768 = {"d_model": 768, "n_heads": 12, "n_layers": 8, "memory_len": 32}
D1024 = {"d_model": 1024, "n_heads": 16, "n_layers": 8, "memory_len": 32}
D1024L16 = {"d_model": 1024, "n_heads": 16, "n_layers": 16, "memory_len": 32}
D1536 = {"d_model": 1536, "n_heads": 16, "n_layers": 8, "memory_len": 32}
D2048 = {"d_model": 2048, "n_heads": 16, "n_layers": 8, "memory_len": 32}
BASE = {"burn_in_steps": 2, "observation": True, "seq_attention": "flash",
        "compute_dtype": "bfloat16"}

# (name, train-arg overrides, net_args) — 2026-08-01 v5e results in the
# name comments; the bench stage pins the winner (d1024/B64/T64/bf16)
VARIANTS = [
    ("B64_T32_bf16", {**BASE, "batch_size": 64, "forward_steps": 30}, D768),    # 0.253
    ("B128_T32_bf16", {**BASE, "batch_size": 128, "forward_steps": 30}, D768),  # 0.247
    ("B64_T64_bf16", {**BASE, "batch_size": 64, "forward_steps": 62}, D768),    # 0.311
    ("B64_T32_fp32", {k: v for k, v in BASE.items() if k != "compute_dtype"}
     | {"batch_size": 64, "forward_steps": 30}, D768),                          # 0.247
    ("d1024_B64_T64_bf16", {**BASE, "batch_size": 64, "forward_steps": 62},
     D1024),                                                                    # 0.347
    # fp32 ~= bf16 at these shapes says the step is not matmul-dtype-bound;
    # candidate culprit was the flash kernel at SHORT windows (it proved
    # itself at T1024; at T64/window-32 the O(T^2) einsum is tiny and
    # XLA-fusable).  SETTLED on-chip 2026-08-02: einsum 18.6 ups / MFU 0.48
    # vs flash 13.5 / 0.347 at the pinned shape — the bench stage now pins
    # einsum and auto-mode's flash_min_t=128 rule stands
    ("d1024_B64_T64_einsum",
     {**BASE, "seq_attention": "einsum", "batch_size": 64, "forward_steps": 62},
     D1024),
    # --- beyond-0.49 sweep (2026-08-02): with attention settled on einsum
    # at T64, the remaining MFU lever is matmul size.  All einsum.
    ("d1024L16_B64_T64_einsum",
     {**BASE, "seq_attention": "einsum", "batch_size": 64, "forward_steps": 62},
     D1024L16),
    ("d1536_B64_T64_einsum",
     {**BASE, "seq_attention": "einsum", "batch_size": 64, "forward_steps": 62},
     D1536),
    ("d2048_B64_T64_einsum",
     {**BASE, "seq_attention": "einsum", "batch_size": 64, "forward_steps": 62},
     D2048),
    ("d1024_B128_T64_einsum",
     {**BASE, "seq_attention": "einsum", "batch_size": 128, "forward_steps": 62},
     D1024),
]


def _rebuild_net(reuse, net_args):
    """Swap the net family size while keeping the filled episode store
    (episodes are env-side data, independent of the net)."""
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables

    env = make_env({"env": "Geister", "net": "transformer",
                    "net_args": net_args})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env))
    return {"module": module, "model": model, "store": reuse["store"]}


def main() -> None:
    duration = float(os.environ.get("TUNE_T", "8"))
    # validate the variant filter BEFORE any jax/device touch: a typo must
    # not cost a backend init
    raw_only = os.environ.get("TUNE_ONLY", "").strip()
    only = {s.strip() for s in raw_only.split(",") if s.strip()} or None
    if only:
        unknown = only - {name for name, _, _ in VARIANTS}
        if unknown:
            sys.exit(f"unknown TUNE_ONLY variant(s): {sorted(unknown)}")

    import jax

    dev = jax.devices()[0]
    peak = bench._peak_flops(dev)
    print(f"# device: {dev.device_kind}, peak {peak}", file=sys.stderr)

    reuse = None
    prev_net = None
    for name, over, net_args in VARIANTS:
        if only and name not in only:
            continue
        if reuse is not None and net_args != prev_net:
            reuse = _rebuild_net(reuse, net_args)
        r = bench._train_bench(
            "Geister", over, duration, 1, fill_episodes=8,
            env_overrides={"net": "transformer", "net_args": net_args},
            reuse=reuse,
        )
        reuse = r
        prev_net = net_args
        tokens = over["batch_size"] * 2 * (over["burn_in_steps"] + over["forward_steps"])
        row = {
            "variant": name,
            "updates_per_sec": bench._sig(r["updates_per_sec"]),
            "tokens_per_sec": bench._sig(r["updates_per_sec"] * tokens, 4),
            "flops_per_step": r["flops_per_step"],
            "mfu": bench._sig(r["flops_per_step"] * r["updates_per_sec"] / peak)
            if (r["flops_per_step"] and peak) else None,
        }
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
