"""Scratch profiler for the TicTacToe train-step CPU headline (VERDICT r2 item 5).

Times one jitted sharded train step on the 1-device CPU backend the way
bench.py does, then variants, to find the 0.796x-vs-torch gap.
"""
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench as B  # reuse the bench's store/batch plumbing


def main():
    import numpy as np
    from handyrl_tpu.parallel import TrainContext, make_mesh

    args = B._make_args("TicTacToe", {})
    _, module, model, store = B._fill_store(args, 48)
    mesh = make_mesh(args["mesh"])
    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(model.variables["params"])
    device_batches = [ctx.put_batch(B._sample_batch(store, args)) for _ in range(4)]

    holder = {"state": state, "i": 0}

    def seq_step():
        holder["state"], metrics = ctx.train_step(
            holder["state"], device_batches[holder["i"] % 4], 1e-5
        )
        holder["i"] += 1
        return metrics["total"]

    ups = B._timed_loop(seq_step, 8.0)
    print(f"baseline ctx.train_step: {ups:.2f} updates/s "
          f"({ups * args['batch_size'] * args['forward_steps']:.0f} env-steps/s)")

    # variant: raw bound jit call, no dispatch_serialized block
    fn = ctx._bind(holder["state"])
    lr = jax.numpy.float32(1e-5)

    def raw_step():
        holder["state"], metrics = fn(holder["state"], device_batches[holder["i"] % 4], lr)
        holder["i"] += 1
        return metrics["total"]

    ups2 = B._timed_loop(raw_step, 8.0)
    print(f"raw jit (no dispatch lock/block): {ups2:.2f} updates/s")

    # variant: fused k=8 scan path on CPU
    try:
        stacked = ctx.put_batches([B._sample_batch(store, args) for _ in range(8)])

        def fused_step():
            holder["state"], metrics = ctx.train_steps(holder["state"], stacked, 1e-5)
            return metrics["total"]

        ups3 = B._timed_loop(fused_step, 8.0) * 8
        print(f"fused k=8 scan: {ups3:.2f} updates/s")
    except Exception as e:
        print("fused failed:", e)

    # cost analysis: where do the flops go?
    flops = ctx.flops_per_step(holder["state"], device_batches[0])
    print(f"flops/step: {flops}")


if __name__ == "__main__":
    main()
