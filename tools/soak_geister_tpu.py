"""Geister learning soak on the real chip through the turn-based/recurrent
device-resident replay (runtime/device_replay.py turn mode).

The committed CPU soak (tests/test_soak.py::test_geister_drc_beats_random)
drives the HOST actor path (thread workers, host replay) and is sized for a
1-core CI host.  This driver is the chip-side complement: GeisterNet's DRC
ConvLSTM trained ONLY by streaming device self-play — records ingested into
device rings, burn-in windows sampled and stepped on device (UPGO targets,
burn-in 4) — then verified with a matched offline eval, trained vs the SAME
net untrained, each over seat-balanced games vs random.

Run (one process holds the chip; the eval child is pinned to the CPU):

    cd /root/repo && nohup python tools/soak_geister_tpu.py train \
        > docs/captures/soak_geister_tpu.log 2>&1 &

Margin: Geister outcomes are {-1, 0, +1} (win/draw/loss, geister.py
outcome); per-game std <= 1, so each 240-game mean outcome has
se <= 0.065 and the matched difference se <= 0.092 — a +0.20 margin keeps
the no-learning false-pass rate under ~2%.  The verdict drives the exit
code (tools/_soak_tpu_common.py).

Result 2026-07-31 (TPU v5 lite x1): wp 0.519 -> 0.694, mean outcome
+0.037 -> +0.388 — 15,740 DRC updates / 45,300 episodes in ~10 min.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._soak_tpu_common import run  # noqa: E402

RUN_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "soak_geister_tpu_run")

CFG = {
    "env_args": {"env": "Geister"},
    "train_args": {
        "turn_based_training": True,
        "observation": True,
        "batch_size": 16,
        "forward_steps": 8,
        "burn_in_steps": 4,
        "policy_target": "UPGO",
        "value_target": "UPGO",
        # near-parity schedule: the chip delivers tens of thousands of
        # updates, so the CPU soak's 16x boost is not needed; 1e-2 entropy
        # bonus for the same reason as the committed soak (1e-1 pins a
        # self-play run at the uniform policy)
        "lr_scale": 2.0,
        "entropy_regularization": 1.0e-2,
        "minimum_episodes": 300,
        "update_episodes": 300,
        "maximum_episodes": 8000,
        "epochs": 150,
        "num_batchers": 1,
        "eval_rate": 0.0,          # workers are eval-only under device_replay
        "device_rollout_games": 64,
        "device_replay": True,
        "device_replay_slots": 512,   # > max episode length 202 + window
        "device_replay_k_steps": 32,
        # dense per-epoch curve vs device random (Geister has no rule-based
        # device twin); the host worker's curve starved on the first capture
        "device_eval_games": 32,
        "fused_steps": 4,
        "mesh": {"dp": 1},
        "worker": {"num_parallel": 1},
        "eval": {"opponent": ["random"]},
    },
}

if __name__ == "__main__":
    run(sys.argv, os.path.abspath(__file__), CFG, RUN_DIR,
        opponent="random", margin=0.20, wp_bar=0.55)
