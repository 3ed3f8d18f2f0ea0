"""Close the bf16-on-conv question with an on-chip HLO profile (VERDICT r3
item 8).

History: round 2 measured bf16 geese training 2.9x SLOWER than fp32 on the
chip; round 3 measured it 1.19-1.32x FASTER — but only because dispatch
latency dominated those captures (smaller transfers win when dispatch is
the bottleneck).  The per-op question — do the 7x11/32-channel convs
themselves run faster or slower in bf16? — was never answered.  This
times the jitted geese train step fp32 vs bf16 with DEVICE timing
decoupled from dispatch (fused lax.scan of K updates per call, so one
dispatch amortizes over K steps and the wall clock approaches pure device
time), and writes jax.profiler traces of both variants for HLO-level
inspection.

Run on the chip:  python tools/profile_bf16.py [K] [reps]
Outputs: docs/captures/bf16_profile_<ts>/ {fp32,bf16}/ trace dirs + a
printed verdict line to paste into BASELINE.md.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    K = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 5

    import jax

    import bench

    print(f"backend: {jax.default_backend()} ({jax.devices()[0].device_kind})")
    ts = time.strftime("%Y-%m-%d_%H%M")
    outdir = f"docs/captures/bf16_profile_{ts}"

    # one shared store of episodes; both variants train the same data
    overrides = {"turn_based_training": False, "observation": False}
    base = bench._train_bench("HungryGeese", overrides, 2.0,
                              len(jax.devices()), fill_episodes=48)

    results = {}
    for name, dtype in (("fp32", None), ("bf16", "bfloat16")):
        if dtype is None:
            res = base  # fp32 IS the base config; no need to re-bench it
        else:
            res = bench._train_bench(
                "HungryGeese", dict(overrides, compute_dtype=dtype),
                2.0, len(jax.devices()), reuse=base,
            )
        ctx, args, store = res["ctx"], res["args"], res["store"]
        state = ctx.init_state(base["model"].variables["params"])
        stacked = ctx.put_batches(
            [bench._sample_batch(store, args) for _ in range(K)]
        )
        state, m = ctx.train_steps(state, stacked, 1e-5)  # compile + warm
        jax.block_until_ready(m["total"])

        times = []
        trace_dir = os.path.join(outdir, name)
        for i in range(reps):
            if i == reps - 1:  # profile only the last rep (smallest trace)
                jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            state, m = ctx.train_steps(state, stacked, 1e-5)
            jax.block_until_ready(m["total"])
            times.append(time.perf_counter() - t0)
            if i == reps - 1:
                jax.profiler.stop_trace()
        per_step_ms = min(times) / K * 1000.0
        results[name] = per_step_ms
        print(f"{name}: {per_step_ms:.3f} ms/update (K={K} fused, best of "
              f"{reps}; all reps {[round(t / K * 1000, 3) for t in times]}) "
              f"trace -> {trace_dir}")

    ratio = results["fp32"] / results["bf16"]
    verdict = ("bf16 FASTER" if ratio > 1.05
               else "bf16 SLOWER" if ratio < 0.95 else "parity")
    print(
        f"VERDICT: {verdict} — fp32 {results['fp32']:.3f} ms/update vs "
        f"bf16 {results['bf16']:.3f} ms/update ({ratio:.2f}x), fused K={K} "
        f"(dispatch amortized; this is device math, not dispatch latency)"
    )


if __name__ == "__main__":
    main()
