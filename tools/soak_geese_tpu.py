"""HungryGeese learning soak on the real chip, through device-resident replay.

The committed CPU soak (tests/test_soak.py::test_geese_device_selfplay_beats_rulebase)
is sized for a 1-core CI host: ~600 updates at lr_scale 8 over hours.  On the
chip the same loop runs at ~50 updates/s (BASELINE.md northstar2 row), so this
driver trains with a near-parity schedule (lr_scale 2) and a tens-of-thousands
update budget — the scale the reference's lr schedule (train.py:328-332,
3e-8 x data-count EMA) was designed for — in tens of minutes.

Run (one process holds the chip; the eval child is pinned to the CPU):

    cd /root/repo && nohup python tools/soak_geese_tpu.py train \
        > docs/captures/soak_geese_tpu.log 2>&1 &

Phase 1 (this process, TPU): Learner.run() with device_replay — self-play,
ring ingest and SGD all on device; host workers eval-only.  Artifacts land in
./soak_geese_tpu_run/ (metrics.jsonl + models/latest.ckpt).
Phase 2 (subprocess, CPU-pinned): matched 240-game evals — the trained net and
the SAME net untrained, each vs 3 greedy rule-based seats
(envs/hungry_geese.py rule_based_action) — identical margin calibration to the
committed soak: mean-outcome difference se <= 0.068, +0.12 margin.  The
verdict drives the exit code (tools/_soak_tpu_common.py).

Result 2026-07-31 (TPU v5 lite x1): wp 0.531 -> 0.733, mean outcome
-0.221 -> +0.110 — 4,944 updates / 100,500 episodes in ~17 min.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._soak_tpu_common import run  # noqa: E402

RUN_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "soak_geese_tpu_run")

CFG = {
    "env_args": {"env": "HungryGeese"},
    "train_args": {
        "turn_based_training": False,
        "observation": False,
        "batch_size": 32,
        "forward_steps": 16,
        "lambda": 0.95,
        # near-parity schedule: the chip delivers the update counts the
        # reference schedule assumes, so the 8x CPU-soak boost is not needed
        "lr_scale": 2.0,
        "minimum_episodes": 500,
        "update_episodes": 500,
        "maximum_episodes": 8000,
        "epochs": 200,
        "num_batchers": 1,
        "eval_rate": 0.0,          # workers are eval-only under device_replay
        "device_rollout_games": 64,
        "device_replay": True,
        # dense per-epoch curve vs the rule-based twin — the host worker's
        # curve starved on this run's first capture (runtime/device_eval.py)
        "device_eval_games": 32,
        "fused_steps": 4,          # 4 updates per dispatch
        "mesh": {"dp": 1},
        "worker": {"num_parallel": 1},
        "eval": {"opponent": ["rulebase"]},
    },
}

if __name__ == "__main__":
    run(sys.argv, os.path.abspath(__file__), CFG, RUN_DIR,
        opponent="rulebase", margin=0.12, wp_bar=0.5)
