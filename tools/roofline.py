"""Roofline analysis of the game-net train steps (VERDICT r4 #5).

The bench's honest game-net MFUs are small (r4 chip capture: tictactoe
0.0154, geese 0.0356, northstar2 0.0194) and BASELINE.md asserts
"model-size artifact, not framework overhead".  This tool PROVES or
REFUTES that from the compiled programs themselves: for each stage's
exact train step it pulls XLA cost analysis (flops + bytes accessed),
computes arithmetic intensity AI = flops/bytes, and compares against the
chip's ridge point peak_flops/hbm_bw (v5e: 197e12/819e9 = 240
flops/byte).  A step with AI far below the ridge is bandwidth-bound and
its MFU CEILING is AI * bw / peak — if the measured MFU sits near that
ceiling, the small number is physics, not overhead; if far below, the
framework is leaving throughput on the table.

Run on the chip for the real fusion/layout numbers
(`python tools/roofline.py`).  Writes docs/captures/roofline_<stamp>.json and prints a
human summary; docs/performance.md carries the conclusions.
"""

from __future__ import annotations

import datetime
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cost(ctx, state, device_batch):
    """(flops, bytes_accessed, source) from XLA cost analysis.

    The COMPILED executable's analysis is authoritative — it reflects
    post-fusion bytes, and the published methodology (performance.md's
    roofline table) is compiled-program numbers; the lowered
    (pre-optimization) analysis overcounts bytes ~2-3x and is kept only
    as a last resort for backends whose executables don't answer.
    'source' is recorded in the capture so the two are never conflated."""
    lowered = ctx._bind(state).lower(
        state, device_batch, __import__("jax").numpy.float32(1e-5)
    )
    errs = []
    for source, ca in (
        ("compiled", lambda: lowered.compile().cost_analysis()),
        ("lowered", lambda: lowered.cost_analysis()),
    ):
        try:
            got = ca()
        except Exception as exc:
            errs.append(exc)
            print(f"[roofline] {source} cost analysis failed: {exc!r}",
                  file=sys.stderr, flush=True)
            continue
        if isinstance(got, (list, tuple)):
            got = got[0] if got else None
        if got:
            return (float(got.get("flops", 0.0)),
                    float(got.get("bytes accessed", 0.0)), source)
    raise RuntimeError(
        "XLA cost analysis unavailable from both the compiled and the "
        "lowered program on this backend"
    ) from (errs[-1] if errs else None)


def stage(env_name: str, overrides: dict, measured_mfu_key: str):
    import jax

    import bench
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.parallel.train_step import (
        hbm_bandwidth_per_chip, peak_flops_per_chip,
    )

    args = bench._make_args(env_name, overrides)
    n_dev = len(jax.devices())
    if args["batch_size"] % n_dev:
        args["batch_size"] = max(n_dev, args["batch_size"] // n_dev * n_dev)
    _, module, model, store = bench._fill_store(args, 16)
    mesh = make_mesh(args["mesh"])
    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(model.variables["params"])
    host_batch = bench._sample_batch(store, args)
    db = ctx.put_batch(host_batch)
    flops, nbytes, cost_source = _cost(ctx, state, db)

    dev = jax.devices()[0]
    peak = peak_flops_per_chip(dev)
    bw = hbm_bandwidth_per_chip(dev)
    out = {
        "env": env_name,
        "batch_size": args["batch_size"],
        "forward_steps": args["forward_steps"],
        "flops_per_step": flops,
        "bytes_accessed_per_step": nbytes,
        "arithmetic_intensity": round(flops / nbytes, 3) if nbytes else None,
        "cost_source": cost_source,
        "measured_mfu_key": measured_mfu_key,
    }
    if peak and bw and nbytes:
        ridge = peak / bw
        ai = flops / nbytes
        out["ridge_flops_per_byte"] = round(ridge, 1)
        out["bandwidth_bound"] = ai < ridge
        # MFU ceiling if the step were perfectly streamed at full HBM bw
        out["mfu_ceiling_at_bw"] = round(min(1.0, ai * bw / peak), 4)
        # equivalently: the fastest possible step time is bytes/bw
        out["min_step_time_us_at_bw"] = round(nbytes / bw * 1e6, 1)

    # bytes-after-quantization column (docs/performance.md §Low-precision):
    # what the int8 fast path removes from the stage's byte traffic.  The
    # weight figure is the serving-engine residency shrink (per-channel
    # int8 codes + fp32 scales vs fp32 kernels); the obs figure is the
    # batch's observation planes at 1-byte width (the int8 obs/wire
    # plane).  The *_int8_est roofline keys are an ESTIMATE — cost
    # analysis of the fp32 program minus the byte savings — not a
    # compiled int8 program; they bound the AI shift, they don't measure
    # post-fusion layout.
    from handyrl_tpu.models.quantize import param_bytes, quantize_params

    wb_fp32 = param_bytes(model.variables["params"])
    wb_int8 = param_bytes(quantize_params(model.variables["params"]))
    obs_leaves = jax.tree.leaves(host_batch["observation"])
    ob_fp32 = sum(int(x.size) * 4 for x in obs_leaves)
    ob_int8 = sum(int(x.size) for x in obs_leaves)
    out["weight_bytes_fp32"] = wb_fp32
    out["weight_bytes_int8"] = wb_int8
    out["obs_bytes_per_step_fp32"] = ob_fp32
    out["obs_bytes_per_step_int8"] = ob_int8
    if nbytes:
        saved = (wb_fp32 - wb_int8) + (ob_fp32 - ob_int8)
        nbytes_q = max(nbytes - saved, 1.0)
        out["bytes_accessed_per_step_int8_est"] = nbytes_q
        out["arithmetic_intensity_int8_est"] = round(flops / nbytes_q, 3)
        if peak and bw:
            out["mfu_ceiling_at_bw_int8_est"] = round(
                min(1.0, flops / nbytes_q * bw / peak), 4
            )
    return out


def main() -> None:
    import jax

    dev = jax.devices()[0]
    platform = f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
    print(f"[roofline] platform {platform}", file=sys.stderr, flush=True)

    results = {
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "platform": platform,
        "note": (
            "bytes accessed / flops from XLA cost analysis of the exact "
            "bench train steps; AI vs ridge point decides bandwidth- vs "
            "compute-bound; mfu_ceiling_at_bw is the physics limit at "
            "full HBM streaming"
        ),
        "stages": [],
    }
    for env_name, over, key in (
        ("TicTacToe", {}, "tictactoe_mfu"),
        ("HungryGeese", {"turn_based_training": False, "observation": False},
         "geese_mfu"),
    ):
        print(f"[roofline] analyzing {env_name}...", file=sys.stderr, flush=True)
        results["stages"].append(stage(env_name, over, key))

    print(json.dumps(results, indent=2))
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d_%H%M")
    dest = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "captures", f"roofline_{stamp}.json",
    )
    with open(dest, "w") as f:
        json.dump(results, f, indent=2)
    print(f"[roofline] wrote {dest}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
