"""Controlled sampler comparison: host EpisodeStore vs device rings
(VERDICT r4 #7, corrected design).

The first attempt compared product `--train` runs at an equal EPISODE
budget — and measured the wrong thing: on-device generation meets the
per-epoch episode budget ~100x faster than host workers, so the device
runs took ~100x fewer SGD steps (26 vs 3,195 on geese) and the curves
compared produce/consume geometry, not sampling semantics.  (Those runs
are still recorded as product context in the output.)

This harness holds EVERYTHING else equal and varies only the SAMPLER:

  shared   one streaming on-device self-play engine
           (`StreamingDeviceRollout` / `build_streaming_fn`),
           one TrainContext, one update budget, one fixed
           rollout:train cadence, one eval protocol;
  A (host) finished episodes -> host `EpisodeStore` -> the reference's
           sampling semantics: per-episode acceptance curve + recency
           bias + per-episode window draw (`runtime/replay.py`,
           reference train.py:292-316) -> make_batch -> train_step;
  B (ring) rollout records -> per-lane device rings -> uniform window
           starts over eligible steps, ring-capacity recency
           (`runtime/device_replay.py`) -> fused sample+train.

Both arms see the same number of updates AND the same generation
stream shape, so the late-mean win-rate delta IS the cost (or not) of
the device ring's two documented sampling deviations.  Writes
docs/captures/sampler_ablation_<stamp>.json; `device_replay.py`'s
docstring quotes the number.
"""

from __future__ import annotations

import datetime
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _common(seed: int):
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.parallel import TrainContext, make_mesh

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
                "policy_target": "UPGO",
                "value_target": "TD",
                "seed": seed,
            },
        }
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    env = make_env(args["env"])
    module = env.net()
    params = init_variables(module, env)["params"]
    mesh = make_mesh(args["mesh"])
    ctx = TrainContext(module, args, mesh)
    return env, module, params, mesh, ctx, args


def _eval_curve_point(evaluator, params, eval_games, key):
    from handyrl_tpu.runtime.evaluation import wp_func

    return wp_func(evaluator.evaluate(params, eval_games, key))


def run_arm(arm: str, total_updates: int, rollouts_per_update: float,
            eval_every: int, eval_games: int, n_lanes: int, seed: int) -> dict:
    """One arm: `arm` in ('host', 'ring'); a rollout dispatch advances all
    lanes k steps; `rollouts_per_update` sets the shared data cadence."""
    import random as _pyrandom

    import jax

    from handyrl_tpu.parallel.mesh import dispatch_serialized
    from handyrl_tpu.runtime.device_eval import DeviceEvaluator

    # the host arm's EpisodeStore.sample_window draws from the global
    # `random` (the product path seeds it in Learner.__init__); seed it
    # here so --seed controls BOTH arms and captures are reproducible
    _pyrandom.seed(seed)
    env, module, params, mesh, ctx, args = _common(seed)
    venv = env.vector_env()
    k_steps = 32
    state = ctx.init_state(params)
    evaluator = DeviceEvaluator(venv, module, n_lanes=32, opponent="random",
                                mesh=mesh if mesh.size > 1 else None)
    key = jax.random.PRNGKey(seed)

    if arm == "ring":
        from handyrl_tpu.runtime.device_replay import DeviceReplay
        from handyrl_tpu.runtime.device_rollout import build_streaming_fn

        fn = build_streaming_fn(venv, module, n_lanes, k_steps,
                                mesh=mesh if mesh.size > 1 else None,
                                use_observe_mask=False)
        replay = DeviceReplay(venv, module, args, mesh, n_lanes, slots=256)
        vstate = venv.init(n_lanes, jax.random.PRNGKey(seed + 1))
        hidden = module.initial_state((n_lanes, venv.num_players))

        def rollout():
            nonlocal vstate, hidden, key
            key, sub = jax.random.split(key)
            vstate, hidden, records = dispatch_serialized(
                lambda: fn(state["params"], vstate, hidden, sub)
            )
            replay.ingest(records)

        while replay.eligible_count() < args["batch_size"]:
            rollout()
        train = replay.train_fn(ctx, fused_steps=1)

        def train_once():
            nonlocal state, key
            key, sub = jax.random.split(key)
            state, m = train(state, sub, 3e-5)
            return m
    else:
        from handyrl_tpu.runtime import EpisodeStore, make_batch
        from handyrl_tpu.runtime.device_rollout import StreamingDeviceRollout

        roll = StreamingDeviceRollout(
            venv, module, args, n_lanes=n_lanes, k_steps=k_steps,
            mesh=mesh if mesh.size > 1 else None,
        )
        store = EpisodeStore(args["maximum_episodes"])
        rkey = [jax.random.PRNGKey(seed + 1)]

        def rollout():
            rkey[0], sub = jax.random.split(rkey[0])
            eps = roll.generate(state["params"], sub)
            if eps:
                store.extend(eps)

        # warm-up gate symmetric with the ring arm's (>= batch_size
        # eligible window starts): roll until the store holds at least
        # batch_size episodes (every episode contributes >= 1 window)
        while len(store) < args["batch_size"]:
            rollout()

        def _batch():
            windows = []
            while len(windows) < args["batch_size"]:
                w = store.sample_window(
                    args["forward_steps"], args["burn_in_steps"],
                    args["compress_steps"],
                )
                if w is not None:
                    windows.append(w)
            return ctx.put_batch(make_batch(windows, args))

        def train_once():
            nonlocal state
            state, m = ctx.train_step(state, _batch(), 3e-5)
            return m
    # shared cadence loop
    curve = []
    pending = 0.0
    t0 = time.perf_counter()
    m = None
    for u in range(1, total_updates + 1):
        pending += rollouts_per_update
        while pending >= 1.0:
            rollout()
            pending -= 1.0
        m = train_once()
        if u % eval_every == 0 or u == total_updates:
            key, ek = jax.random.split(key)
            wp = _eval_curve_point(evaluator, state["params"], eval_games, ek)
            curve.append({"updates": u, "win_points": round(wp, 4)})
            print(f"  [{arm}] {u}/{total_updates} updates, wp = {wp:.3f}",
                  file=sys.stderr, flush=True)
    if arm == "host":
        roll.drain()
    else:
        replay.drain()
    import numpy as np

    total = float(jax.device_get(m["total"]))
    late = [c["win_points"] for c in curve if c["updates"] >= total_updates * 2 // 3]
    return {
        "arm": arm,
        "updates": total_updates,
        "curve": curve,
        "late_mean_win_points": round(sum(late) / max(len(late), 1), 4),
        "wall_s": round(time.perf_counter() - t0, 1),
        "loss_finite": bool(np.isfinite(total)),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=300)
    ap.add_argument("--rollouts-per-update", type=float, default=0.25)
    ap.add_argument("--eval-every", type=int, default=50)
    ap.add_argument("--eval-games", type=int, default=64)
    ap.add_argument("--lanes", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()

    out = {
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "env": "HungryGeese",
        "params": {"updates": a.updates,
                   "rollouts_per_update": a.rollouts_per_update,
                   "eval_every": a.eval_every, "eval_games": a.eval_games,
                   "lanes": a.lanes, "seed": a.seed},
        "design": (
            "one on-device generation engine, one TrainContext, equal "
            "updates and rollout cadence; only the sampler differs "
            "(host EpisodeStore acceptance/recency/per-episode windows "
            "vs device rings' uniform-step windows + capacity recency)"
        ),
        "arms": [],
    }
    for arm in ("host", "ring"):
        print(f"[sampler-ablate] arm={arm}...", file=sys.stderr, flush=True)
        out["arms"].append(
            run_arm(arm, a.updates, a.rollouts_per_update, a.eval_every,
                    a.eval_games, a.lanes, a.seed)
        )
    host, ring = out["arms"]
    out["delta_late_mean"] = round(
        ring["late_mean_win_points"] - host["late_mean_win_points"], 4
    )
    print(json.dumps(out, indent=2))
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d_%H%M")
    dest = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "docs", "captures", f"sampler_ablation_{stamp}.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[sampler-ablate] wrote {dest}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
