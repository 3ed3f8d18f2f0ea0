"""Replay-ratio / staleness ablation for the north-star loop (VERDICT r4 #4).

A loop that trains many times a rollout re-samples each ring window many
times (about 60x at trains_per_rollout=16).  The soaks passed in that
regime, but nothing showed WHERE learning degrades as the ratio grows —
the most load-bearing untested assumption in the perf story.  This tool
measures it: the north-star loop's shape (streaming on-device HungryGeese
self-play -> device rings -> fused sample+train, self-play always under
the latest params; the benchmark's `geese_loop` cell runs it through
`Learner`), but run for LEARNING — a fixed
budget of UPDATES per configuration, win rate vs random evaluated every
``eval_every`` updates through DeviceEvaluator, so the curves are
win-rate-vs-updates at trains_per_rollout in {1, 4, 16, 64}.

Higher trains_per_rollout = less fresh data per update = higher
effective replay ratio/staleness.  If the 64 curve tracks the 1 curve,
the V-Trace/UPGO off-policy corrections are carrying the regime; where
it sags is the measured staleness limit, and a shipped default must sit
below it.  Off-policy corrections anchor: reference train.py:230-239.

CPU mesh is fine (the ratio is a data-freshness property, not a device
property).  Writes docs/captures/replay_ratio_ablation_<stamp>.json.
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RATIOS = (1, 4, 16, 64)


def run_config(trains_per_rollout: int, total_updates: int, eval_every: int,
               eval_games: int, n_lanes: int, seed: int) -> dict:
    import jax

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime.device_eval import DeviceEvaluator
    from handyrl_tpu.runtime.device_replay import DeviceReplay
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn
    from handyrl_tpu.runtime.evaluation import wp_func
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.parallel.mesh import dispatch_serialized

    cfg = normalize_args(
        {
            "env_args": {"env": "HungryGeese"},
            "train_args": {
                "turn_based_training": False,
                "observation": False,
                "burn_in_steps": 0,
                "forward_steps": 8,
                "batch_size": 32,
                "compress_steps": 4,
                "seed": seed,
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]

    env = make_env(args["env"])
    venv = env.vector_env()
    module = env.net()
    params = init_variables(module, env)["params"]
    mesh = make_mesh(args["mesh"])

    k_steps = 32
    fn = build_streaming_fn(
        venv, module, n_lanes, k_steps,
        mesh=mesh if mesh.size > 1 else None, use_observe_mask=False,
    )
    replay = DeviceReplay(venv, module, args, mesh, n_lanes, slots=256)
    ctx = TrainContext(module, args, mesh)
    state = ctx.init_state(params)
    train = replay.train_fn(ctx, fused_steps=1)
    evaluator = DeviceEvaluator(venv, module, n_lanes=32, opponent="random",
                                mesh=mesh if mesh.size > 1 else None)

    key = jax.random.PRNGKey(seed)
    vstate = venv.init(n_lanes, jax.random.PRNGKey(seed + 1))
    hidden = module.initial_state((n_lanes, venv.num_players))

    def rollout():
        nonlocal vstate, hidden, key
        key, sub = jax.random.split(key)
        vstate, hidden, records = dispatch_serialized(
            lambda: fn(state["params"], vstate, hidden, sub)
        )
        return replay.ingest(records)

    # prefill until a batch is sampleable
    while replay.eligible_count() < args["batch_size"]:
        rollout()

    curve = []
    updates = 0
    produced_steps = 0
    t0 = time.perf_counter()
    while updates < total_updates:
        stats = rollout()
        produced_steps += int(jax.device_get(stats["game_steps"]))
        for _ in range(trains_per_rollout):
            if updates >= total_updates:
                break
            key, sub = jax.random.split(key)
            state, m = train(state, sub, 3e-5)
            updates += 1
            if updates % eval_every == 0 or updates == total_updates:
                key, ek = jax.random.split(key)
                counts = evaluator.evaluate(state["params"], eval_games, ek)
                wp = wp_func(counts)
                curve.append({"updates": updates, "win_points": round(wp, 4)})
                print(f"  [ratio {trains_per_rollout}] {updates}/"
                      f"{total_updates} updates, wp vs random = {wp:.3f}",
                      file=sys.stderr, flush=True)
    consumed = updates * args["batch_size"] * args["forward_steps"]
    total = float(jax.device_get(m["total"]))
    return {
        "trains_per_rollout": trains_per_rollout,
        "updates": updates,
        "produce_consume_ratio": round(produced_steps / consumed, 5),
        "effective_replay_ratio": round(consumed / max(produced_steps, 1), 1),
        "curve": curve,
        "final_win_points": curve[-1]["win_points"] if curve else None,
        "late_mean_win_points": round(
            sum(c["win_points"] for c in curve[-3:]) / max(len(curve[-3:]), 1), 4
        ),
        "wall_s": round(time.perf_counter() - t0, 1),
        "loss_finite": bool(__import__("numpy").isfinite(total)),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=400)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--eval-games", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ratios", default=",".join(map(str, RATIOS)))
    a = ap.parse_args()

    results = {
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "env": "HungryGeese (device-replay northstar loop)",
        "budget_updates_each": a.updates,
        "configs": [],
    }
    for r in (int(x) for x in a.ratios.split(",")):
        print(f"[ablate] trains_per_rollout={r}...", file=sys.stderr, flush=True)
        results["configs"].append(
            run_config(r, a.updates, a.eval_every, a.eval_games, a.lanes, a.seed)
        )

    print(json.dumps(results, indent=2))
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d_%H%M")
    dest = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "docs", "captures", f"replay_ratio_ablation_{stamp}.json")
    with open(dest, "w") as f:
        json.dump(results, f, indent=2)
    print(f"[ablate] wrote {dest}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
