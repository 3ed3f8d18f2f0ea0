"""The readings a configuration's limits are set from, and the 8-bit control,
through the comparison that decides ``correct``.

    python3 benchmark/limit_readings.py --workload <cell> --seeds 11 12 13 ...

A ``train_step`` runner judges one seed a run and has no switch for the
control.  This is that runner's last paragraph alone, many seeds in one
process: for each seed the runner's own set-up (the seeded weights, the cell's
batches of seeded random play, the routers' selection biases balanced on them:
``traffic.balanced_params``) and the first batch's first row, the row a run of
that seed judges, handed to ``harness.judge_forward`` twice: once as they are (``sound``)
and once with the system's two forwards reading weights rounded leaf by leaf to
float8 e4m3 (``8bit``: rounded eagerly, outside any jit, since inside one XLA
keeps the excess precision and rounds nothing).  The reference always reads the
sound weights.  One JSON line a reading on stdout (``seed``, ``weights``,
``checks``, and ``compared``: each number beside its limit, the result line's
own key), appended to ``benchmark_out/limit_readings/<cell>.jsonl`` too.

Exit 0 where every sound reading passes every check and every 8-bit one fails
at least one, else 1.  The 8-bit control rounds the balanced biases with every
other leaf.  ``judge_forward`` jits its programs
anew in every call, so a reading pays their traces again (the compile cache
holds what they lower to): about a minute a seed at the published sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SOUND, ROUNDED = "sound", "8bit"


def readings(root: str, workload: str, seeds):
    """Yield one reading a (seed, weights): ``judge_forward``'s checks and
    what it compared, for the sound weights and for the 8-bit control."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness, traffic
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.parallel.train_step import forward_prediction

    run = harness.Run(root, workload, seeds[0], 1.0, False, True, time.monotonic())
    cell, config = run.cell, run.config
    reference_rows = run.reference().forward_rows

    def reference(weights, batch, config, burn_in, choices=None):
        return reference_rows(weights[SOUND], batch, config, burn_in, choices=choices)

    for seed in seeds:
        cfg = normalize_args({
            "env_args": dict(config["env_args"]),
            "train_args": dict(config.get("train_args", {}), **cell["train_args"], seed=seed)})
        args = dict(cfg["train_args"], env=cfg["env_args"])
        random.seed(seed)
        np.random.seed(seed)
        env = make_env(args["env"])
        module = env.net()
        run.require_module(module)

        def system(weights, batch, dtype=args.get("compute_dtype")):
            return forward_prediction(module, traffic.in_compute_dtype(weights["read"], dtype),
                                      batch, dict(args, compute_dtype=dtype))

        # the runner's set-up: the cell's batches, the routers balanced on them
        host_batches = traffic.random_play_batches(
            env, module, args, int(cell["n_batches"]), int(cell["fill_episodes"]))
        ctx = TrainContext(module, args, make_mesh(cell["mesh"], devices=jax.devices()[:run.chips]))
        params, _, balance = traffic.balanced_params(
            module, traffic.seeded_params(module, env, seed), ctx.args,
            [ctx.put_batch(b) for b in host_batches])
        row = jax.tree.map(lambda x: np.asarray(x)[:1], host_batches[0])
        del host_batches
        burn_in = int(args["burn_in_steps"])
        legal = (row["action_mask"][:, burn_in:] == 0) & (row["turn_mask"][:, burn_in:] > 0)
        observed = row["observation_mask"][:, burn_in:] > 0
        rounded = jax.tree.map(lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), params)
        for weights, read in ((SOUND, params), (ROUNDED, rounded)):
            checks, _, compared = harness.judge_forward(
                system, reference, {SOUND: params, "read": read}, row, config, burn_in,
                mask_of=lambda head: legal if head == "policy" else observed,
                system_f32=lambda w, b: system(w, b, "float32"))
            yield {"seed": seed, "weights": weights, "tokens": int(observed.sum()),
                   "checks": checks, "compared": compared, "router_balance": balance}
        del params, rounded


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--root", default=HERE,
                        help="where workloads/, configs/, reference/ are found")
    opts = parser.parse_args(argv)

    from handyrl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = os.path.join(REPO, "benchmark_out", "limit_readings", opts.workload + ".jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    as_hoped = True
    for reading in readings(os.path.abspath(opts.root), opts.workload, opts.seeds):
        passed = all(reading["checks"].values())
        as_hoped &= passed == (reading["weights"] == SOUND)
        line = json.dumps(reading)
        print(line, flush=True)
        with open(out, "a") as f:
            f.write(line + "\n")
    return 0 if as_hoped else 1


if __name__ == "__main__":
    sys.exit(main())
