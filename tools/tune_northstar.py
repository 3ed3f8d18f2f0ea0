"""Duty-cycle sweep for the north-star v2 loop (VERDICT r3 item 3) and
chip-split sweep for the v3 disaggregated planes.

Round 3 measured `northstar2_rollout_time_frac` 0.957: the chip spent 25x
more time on self-play rollouts than on SGD, so the "107k trained
steps/s" headline was mostly a rollout benchmark.  This sweeps the loop
geometry — lanes x k_steps (rollout work per call), fused_steps x
trains_per_rollout (SGD work per iteration) — through the REAL bench
stage (`bench._device_replay_northstar_bench`) and prints one row per
combo, so the knee (rollout_time_frac <= 0.5 with self-play still
outpacing or matching consumption, produce_consume_ratio >= ~0.5) can be
read off and pinned as the bench default + a BASELINE.md row.

`--split` sweeps the v3 plane instead: every actor_chips value of
`plane: split` through `bench._split_plane_northstar_bench` (plus
param_refresh_updates at the default split), so the chip allocation
where trained env-steps/s peaks with produce_consume >= 0.1 — the ratio
is a CHIP knob there, not a duty-cycle compromise — can be read off.
Needs >= 2 devices; on fewer every row reports skipped.

Run ON THE CHIP (falls back to CPU with a warning — CPU ratios are not
representative, but the harness logic can be smoke-tested with
TUNE_QUICK=1).

Usage: python tools/tune_northstar.py [--split] [duration_per_combo_s]
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import time

sys.path.insert(0, "/root/repo")

import bench  # noqa: E402  (repo-root import)


def main() -> None:
    import jax

    split = "--split" in sys.argv[1:]
    argv = [a for a in sys.argv[1:] if a != "--split"]
    duration = float(argv[0]) if argv else 8.0
    quick = bool(os.environ.get("TUNE_QUICK"))
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"WARNING: backend is {backend}; ratios are not TPU-representative",
              file=sys.stderr)

    # geese train context once; reused across combos (same jitted step)
    bench._note("tune: building geese train context + store")
    gt = bench._train_bench(
        "HungryGeese", {"turn_based_training": False, "observation": False},
        2.0, len(jax.devices()),
        fill_episodes=12 if quick else 48,
    )

    if split:
        _sweep_split(jax, duration, quick, gt)
        return

    if backend != "cpu":
        # the fused loop no longer host-syncs per rollout (async-dispatch
        # satellite fix), so off-CPU rollout_time_frac is the HOST enqueue
        # share, not device duty — bench main() flags the same caveat as
        # northstar2_rollout_time_frac_note; read the knee primarily off
        # produce_consume + trained_steps_per_sec there
        print("NOTE: async dispatch — rollout_time_frac is host-side "
              "enqueue share, not device duty, on this backend",
              file=sys.stderr)

    if quick:
        combos = [(32, 16, 2, t) for t in (1, 4)]
    else:
        combos = list(itertools.product(
            (128, 256),       # n_lanes
            (16, 32),         # k_steps
            (8,),             # fused_steps
            (2, 4, 8, 16),    # trains_per_rollout
        ))
    rows = []
    for lanes, k, fused, trains in combos:
        t0 = time.perf_counter()
        try:
            r = bench._device_replay_northstar_bench(
                gt, duration, n_lanes=lanes, k_steps=k,
                fused_steps=fused, trains_per_rollout=trains,
            )
        except Exception as exc:  # keep sweeping; record the failure
            r = {"skipped": f"{type(exc).__name__}: {exc}"}
        # echo the EFFECTIVE geometry from the bench result (off-TPU the
        # stage clamps lanes/fused_steps; a knee read off requested values
        # would pin a geometry that was never measured)
        row = {"lanes": r.get("lanes", lanes), "k_steps": r.get("k_steps", k),
               "fused": r.get("fused_steps", fused),
               "trains_per_rollout": r.get("trains_per_rollout", trains),
               "wall_s": round(time.perf_counter() - t0, 1)}
        if "skipped" in r:
            row["skipped"] = r["skipped"]
        else:
            row.update(
                updates_per_sec=round(r["updates_per_sec"], 1),
                trained_steps_per_sec=round(r["trained_env_steps_per_sec"], 0),
                selfplay_steps_per_sec=round(r["selfplay_env_steps_per_sec"], 0),
                rollout_time_frac=round(r["rollout_time_frac"], 3),
                produce_consume=round(r["produce_consume_ratio"], 3)
                if r["produce_consume_ratio"] else None,
            )
        rows.append(row)
        print(json.dumps(row), flush=True)

    ok = [r for r in rows if "skipped" not in r]
    # knee: most trained steps/s among combos that keep the loop fed
    fed = [r for r in ok if r["produce_consume"] and r["produce_consume"] >= 0.5]
    if fed:
        best = max(fed, key=lambda r: r["trained_steps_per_sec"])
        print("KNEE:", json.dumps(best))
    elif ok:
        print("KNEE: none kept produce_consume >= 0.5; fastest overall:",
              json.dumps(max(ok, key=lambda r: r["trained_steps_per_sec"])))


def _sweep_split(jax, duration: float, quick: bool, gt) -> None:
    """Sweep the v3 plane: actor_chips (and, at the default split, the
    param refresh cadence) through `bench._split_plane_northstar_bench`.
    One JSON row per combo; the knee is the chip split with the most
    trained env-steps/s among combos keeping produce_consume >= 0.1."""
    n = len(jax.devices())
    if n < 2:
        print(json.dumps({"skipped": f"plane sweep needs >= 2 devices, have {n}"}))
        return
    chips = [1] if quick else list(range(1, n))
    refreshes = [8] if quick else (1, 8, 32)
    combos = [(c, 8) for c in chips]
    default_split = max(1, n // 2)
    combos += [(default_split, r) for r in refreshes if r != 8]
    rows = []
    for actor_chips, refresh in combos:
        t0 = time.perf_counter()
        try:
            r = bench._split_plane_northstar_bench(
                gt, duration, actor_chips=actor_chips,
                param_refresh_updates=refresh,
            )
        except Exception as exc:  # keep sweeping; record the failure
            r = {"skipped": f"{type(exc).__name__}: {exc}"}
        row = {"actor_chips": actor_chips, "learner_chips": n - actor_chips,
               "param_refresh_updates": refresh,
               "wall_s": round(time.perf_counter() - t0, 1)}
        if "skipped" in r:
            row["skipped"] = r["skipped"]
        else:
            row.update(
                trained_steps_per_sec=round(r["trained_env_steps_per_sec"], 0),
                selfplay_steps_per_sec=round(r["selfplay_env_steps_per_sec"], 0),
                selfplay_concurrent_frac=round(r["selfplay_concurrent_frac"], 3)
                if r["selfplay_concurrent_frac"] else None,
                rollout_time_frac=round(r["rollout_time_frac"], 3),
                actor_busy_frac=round(r["actor_busy_frac"], 3),
                param_lag_mean=round(r["param_lag_mean"], 1),
                produce_consume=round(r["produce_consume_ratio"], 3)
                if r["produce_consume_ratio"] else None,
            )
        rows.append(row)
        print(json.dumps(row), flush=True)

    ok = [r for r in rows if "skipped" not in r]
    fed = [r for r in ok if r["produce_consume"] and r["produce_consume"] >= 0.1]
    if fed:
        best = max(fed, key=lambda r: r["trained_steps_per_sec"])
        print("KNEE:", json.dumps(best))
    elif ok:
        print("KNEE: none kept produce_consume >= 0.1; fastest overall:",
              json.dumps(max(ok, key=lambda r: r["trained_steps_per_sec"])))


if __name__ == "__main__":
    main()
