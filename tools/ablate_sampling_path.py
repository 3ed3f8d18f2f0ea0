"""Quantify the device-replay sampling deviations (VERDICT r4 #7).

``runtime/device_replay.py`` deliberately deviates from the host replay's
sampling in two ways (documented in its module docstring): recency bias
comes from ring capacity instead of the reference's per-episode
acceptance curve (reference train.py:292-303), and window starts are
uniform over eligible STEPS (weighting long episodes by window count)
instead of uniform over episodes.  The soaks prove the device path
learns; this tool measures the COST of the deviation: same-budget
`--train` runs through the real product stack — host-path sampling vs
device-ring sampling — on ParallelTicTacToe and HungryGeese, comparing the
win-rate-vs-updates curves from each run's metrics.jsonl.

Both runs of a pair share every train_arg except the data path
(`device_rollout_games` + `device_replay`); equal budget = equal
`epochs` (model updates) at equal `update_episodes`.  Output:
docs/captures/sampling_path_ablation_<stamp>.json with both curves and
the late-mean delta, which device_replay.py's docstring quotes.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE = {
    "turn_based_training": False,
    "observation": False,
    "gamma": 0.8,
    "forward_steps": 8,
    "burn_in_steps": 0,
    "compress_steps": 4,
    "update_episodes": 100,
    "batch_size": 64,
    "minimum_episodes": 200,
    "maximum_episodes": 20000,
    "eval_rate": 0.15,
    "worker": {"num_parallel": 4},
    "lambda": 0.7,
    "policy_target": "UPGO",
    "value_target": "TD",
    "eval": {"opponent": ["random"]},
    "seed": 0,
}

# ParallelTicTacToe stands in for TicTacToe on the device side: the
# device ring needs a STREAMING vector twin (reset_done/step), and
# TicTacToe's twin is episodic — DeviceReplay rejects it at
# construction.  ParallelTicTacToe is the tictactoe-family env with the
# streaming twin + view_obs hook, so the pair isolates exactly the
# sampling-path difference the VERDICT asks about.
PAIRS = {
    "ParallelTicTacToe": {"epochs": 60},
    "HungryGeese": {"epochs": 20},
}


def run_one(env_name: str, device_path: bool, epochs: int, run_root: str,
            timeout_s: float) -> dict:
    import yaml

    tag = "device" if device_path else "host"
    run_dir = os.path.join(run_root, f"{env_name.lower()}_{tag}")
    os.makedirs(run_dir, exist_ok=True)
    train_args = {**BASE, "epochs": epochs}
    if device_path:
        train_args.update(
            {"device_rollout_games": 32, "device_replay": True,
             "device_replay_slots": 256, "device_replay_k_steps": 32,
             # device-replay runs generate nothing on the host, so the
             # win-rate books need the on-device evaluator to fill
             # metrics.jsonl win_rate records
             "device_eval_games": 64}
        )
    with open(os.path.join(run_dir, "config.yaml"), "w") as f:
        yaml.safe_dump(
            {"env_args": {"env": env_name}, "train_args": train_args,
             "worker_args": {"server_address": "", "num_parallel": 4}}, f
        )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    with open(os.path.join(run_dir, "train.log"), "w") as log:
        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "main.py"), "--train"],
            cwd=run_dir, env=env, stdout=log, stderr=subprocess.STDOUT,
            timeout=timeout_s,
        ).returncode
    if rc != 0:
        raise SystemExit(f"{env_name}/{tag} train failed rc={rc}; "
                         f"see {run_dir}/train.log")
    from handyrl_tpu.utils.metrics import read_metrics

    curve = []
    # read_metrics tolerates a truncated tail; win_rate can be an explicit
    # null on epochs with no eval results
    for rec in read_metrics(os.path.join(run_dir, "metrics.jsonl")):
        wr = (rec.get("win_rate") or {}).get("total")
        if wr is not None:
            curve.append({"epoch": rec["epoch"], "win_rate": round(wr, 4)})
    late = [c["win_rate"] for c in curve if c["epoch"] >= epochs * 2 // 3]
    return {
        "path": tag,
        "epochs": epochs,
        "wall_s": round(time.perf_counter() - t0, 1),
        "curve": curve,
        "late_mean_win_rate": round(sum(late) / max(len(late), 1), 4),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--envs", default="ParallelTicTacToe,HungryGeese")
    ap.add_argument("--train-timeout", type=float, default=5400.0)
    ap.add_argument("--run-root",
                    default=os.path.join(REPO, "sampling_ablation_run"))
    a = ap.parse_args()

    out = {
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "pairs": [],
    }
    for env_name in a.envs.split(","):
        epochs = PAIRS[env_name]["epochs"]
        pair = {"env": env_name}
        for device_path in (False, True):
            tag = "device" if device_path else "host"
            print(f"[ablate-sampling] {env_name} {tag} path, "
                  f"{epochs} epochs...", file=sys.stderr, flush=True)
            pair[tag] = run_one(env_name, device_path, epochs, a.run_root,
                                a.train_timeout)
            print(f"[ablate-sampling]   late-mean win rate "
                  f"{pair[tag]['late_mean_win_rate']}", file=sys.stderr,
                  flush=True)
        pair["delta_late_mean"] = round(
            pair["device"]["late_mean_win_rate"]
            - pair["host"]["late_mean_win_rate"], 4
        )
        out["pairs"].append(pair)

    print(json.dumps(out, indent=2))
    stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d_%H%M")
    dest = os.path.join(REPO, "docs", "captures",
                        f"sampling_path_ablation_{stamp}.json")
    with open(dest, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[ablate-sampling] wrote {dest}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
