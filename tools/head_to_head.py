"""Cross-framework head-to-head: equal-budget training QUALITY comparison.

The throughput benches only imply a quality win; this tool measures it
directly (VERDICT r4 item 3).  Both frameworks train TicTacToe under the
reference's own default train_args (/root/reference/config.yaml) and an
EQUAL episode budget — identical minimum_episodes / update_episodes /
epochs, so both consume minimum + epochs*update episodes before their
identical stop condition fires (reference train.py:623-624; repo
runtime/learner.py:450) — then the two trained agents are pitted
directly through this repo's match layer with seat balancing
(runtime/evaluation.py evaluate_mp), both policies sampled at
temperature 1.0 (reference SoftAgent semantics, agent.py:110-112).

The reference's trained net plays through its own torch ModelWrapper
(model.py:33-60, numpy-in/numpy-out) wrapped in THIS repo's Agent; the
observation tensors come from this repo's TicTacToe env, which is
lock-step parity-tested against the reference env
(tools/crosscheck_reference.py), so both nets see exactly the boards
they were trained on.

Usage:
    python tools/head_to_head.py                 # all phases
    python tools/head_to_head.py --phase pit     # reuse existing runs
    python tools/head_to_head.py --epochs 25 --games 600

Writes head2head_run/{ref,ours}/ training runs (gitignored) and a
results JSON + log lines to docs/captures/.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = "/root/reference"
sys.path.insert(0, REPO)

# the reference's own default train_args (reference config.yaml), minus
# the unbounded epochs: -1 — the equal budget needs a bounded stop
COMMON_TRAIN_ARGS = {
    "turn_based_training": True,
    "observation": False,
    "gamma": 0.8,
    "forward_steps": 16,
    "burn_in_steps": 0,
    "compress_steps": 4,
    "entropy_regularization": 1.0e-1,
    "entropy_regularization_decay": 0.1,
    "update_episodes": 200,
    "batch_size": 128,
    "minimum_episodes": 400,
    "maximum_episodes": 100000,
    "num_batchers": 2,
    "eval_rate": 0.1,
    "worker": {"num_parallel": 6},
    "lambda": 0.7,
    "policy_target": "TD",
    "value_target": "TD",
    "eval": {"opponent": ["random"]},
    "seed": 0,
    "restart_epoch": 0,
}


def _write_yaml(path: str, cfg: dict) -> None:
    import yaml

    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)


def _run_train(cmd, cwd, env, log_path, timeout_s: float,
               success_marker=None) -> float:
    t0 = time.perf_counter()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=cwd, env=env, stdout=log, stderr=subprocess.STDOUT
        )
        try:
            rc = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
            raise SystemExit(
                f"training timed out after {timeout_s:.0f}s; see {log_path}"
            )
    if rc != 0:
        # the reference aborts in teardown AFTER completing ("terminate
        # called without an active exception" from its multiprocessing
        # workers -> SIGABRT); completion is judged by the trained
        # artifact + its own success marker, not the exit code
        done_marker = success_marker and _training_completed(
            cwd, log_path, success_marker
        )
        if not done_marker:
            raise SystemExit(f"training failed rc={rc}; see {log_path}")
        print(f"[h2h] note: trainer exited rc={rc} after completing "
              f"(teardown abort); artifact + '{success_marker}' present",
              flush=True)
    return time.perf_counter() - t0


def _training_completed(run_dir: str, log_path: str, marker) -> bool:
    artifact, text = marker
    if not os.path.exists(os.path.join(run_dir, artifact)):
        return False
    with open(log_path, "r", errors="replace") as f:
        return text in f.read()


def ref_train(run_dir: str, epochs: int, timeout_s: float) -> float:
    """Train the reference (torch CPU, its own main.py --train) to
    ``epochs`` model epochs; saves models/latest.pth under run_dir."""
    os.makedirs(run_dir, exist_ok=True)
    _write_yaml(
        os.path.join(run_dir, "config.yaml"),
        {
            "env_args": {"env": "TicTacToe"},
            "train_args": {**COMMON_TRAIN_ARGS, "epochs": epochs},
            "worker_args": {"server_address": "", "num_parallel": 6},
        },
    )
    env = dict(os.environ, PYTHONPATH=REFERENCE)
    # keep torch single-threaded per process: 6 worker processes already
    # oversubscribe the 1-core host; thread fan-out makes it worse
    env.setdefault("OMP_NUM_THREADS", "1")
    return _run_train(
        [sys.executable, os.path.join(REFERENCE, "main.py"), "--train"],
        run_dir, env, os.path.join(run_dir, "train.log"), timeout_s,
        success_marker=(os.path.join("models", "latest.pth"), "finished server"),
    )


def ours_train(run_dir: str, epochs: int, timeout_s: float) -> float:
    """Train this repo (CPU-forced for like-for-like with the torch-CPU
    reference) to ``epochs`` model updates; saves models/latest.ckpt."""
    os.makedirs(run_dir, exist_ok=True)
    _write_yaml(
        os.path.join(run_dir, "config.yaml"),
        {
            "env_args": {"env": "TicTacToe"},
            "train_args": {**COMMON_TRAIN_ARGS, "epochs": epochs},
            "worker_args": {"server_address": "", "num_parallel": 6},
        },
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return _run_train(
        [sys.executable, os.path.join(REPO, "main.py"), "--train"],
        run_dir, env, os.path.join(run_dir, "train.log"), timeout_s,
    )


def _load_ref_agent(run_dir: str, temperature: float):
    """Reference models/latest.pth -> reference torch net + ModelWrapper
    -> THIS repo's sampling Agent."""
    import torch

    sys.path.insert(0, REFERENCE)
    from handyrl.envs.tictactoe import Environment as RefEnv  # noqa: E402
    from handyrl.model import ModelWrapper  # noqa: E402

    from handyrl_tpu.agents import Agent

    net = RefEnv().net()
    path = os.path.join(run_dir, "models", "latest.pth")
    net.load_state_dict(torch.load(path))
    net.eval()
    return Agent(ModelWrapper(net), temperature=temperature, seed=1)


def _load_ours_agent(run_dir: str, temperature: float):
    from handyrl_tpu.agents import Agent
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables
    from handyrl_tpu.runtime.checkpoint import load_params

    env = make_env({"env": "TicTacToe"})
    module = env.net()
    variables = init_variables(module, env)
    params = load_params(
        os.path.join(run_dir, "models", "latest.ckpt"), variables["params"]
    )
    return Agent(
        InferenceModel(module, {"params": params}), temperature=temperature, seed=2
    )


def pit(ref_dir: str, ours_dir: str, games: int, temperature: float) -> dict:
    """Seat-balanced direct match through this repo's match layer; returns
    the result dict with win points from OUR agent's perspective.

    Results land in a league ``PayoffMatrix`` (handyrl_tpu/league) — the
    same ledger league matches and battle-server games record into — so
    this tool, the league's promotion gate, and the sampler ablation all
    report ONE win-points convention (win + draw/2 over games, wp_func)."""
    from handyrl_tpu.league.matchmaker import PayoffMatrix
    from handyrl_tpu.runtime.evaluation import evaluate_mp, wp_func

    ours = _load_ours_agent(ours_dir, temperature)
    ref = _load_ref_agent(ref_dir, temperature)
    results = evaluate_mp(
        {"env": "TicTacToe"}, {0: ours, 1: ref}, games, num_workers=2
    )
    payoff = PayoffMatrix()
    per_pattern = {}
    outcomes_total: dict = {}
    for pat, res in results.items():
        for outcome, count in res.items():
            # evaluate_mp aggregates outcomes from OUR seat's perspective;
            # replay them into the ledger pairwise (zero-sum 2p)
            payoff.record_score("ours", "ref", float(outcome), -float(outcome),
                                n=count)
            outcomes_total[outcome] = outcomes_total.get(outcome, 0) + count
        per_pattern[pat] = {
            "win_points": round(wp_func(res), 4),
            "games": sum(res.values()),
            "outcomes": {str(k): v for k, v in res.items()},
        }
    wp = payoff.win_points("ours", "ref")
    return {
        "ours_win_points": None if wp is None else round(wp, 4),
        "games": payoff.games("ours", "ref"),
        "outcomes_from_ours_perspective": {
            str(k): v for k, v in outcomes_total.items()
        },
        "per_pattern": per_pattern,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["all", "ref-train", "ours-train", "pit"],
                    default="all")
    ap.add_argument("--epochs", type=int, default=25)
    ap.add_argument("--games", type=int, default=600)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--train-timeout", type=float, default=3600.0)
    ap.add_argument("--run-root", default=os.path.join(REPO, "head2head_run"))
    args = ap.parse_args()

    ref_dir = os.path.join(args.run_root, "ref")
    ours_dir = os.path.join(args.run_root, "ours")
    budget = (COMMON_TRAIN_ARGS["minimum_episodes"]
              + args.epochs * COMMON_TRAIN_ARGS["update_episodes"])
    out = {
        "date_utc": datetime.datetime.now(datetime.timezone.utc).isoformat(timespec="seconds"),
        "env": "TicTacToe",
        "config": "reference defaults (reference config.yaml)",
        "epochs": args.epochs,
        "episode_budget_each": budget,
        "pit_games": args.games,
        "temperature": args.temperature,
    }

    if args.phase in ("all", "ref-train"):
        print(f"[h2h] training reference to {args.epochs} epochs "
              f"(~{budget} episodes)...", flush=True)
        out["ref_train_s"] = round(ref_train(ref_dir, args.epochs,
                                             args.train_timeout), 1)
        print(f"[h2h] reference trained in {out['ref_train_s']}s", flush=True)
    if args.phase in ("all", "ours-train"):
        print(f"[h2h] training handyrl_tpu to {args.epochs} epochs "
              f"(~{budget} episodes)...", flush=True)
        out["ours_train_s"] = round(ours_train(ours_dir, args.epochs,
                                               args.train_timeout), 1)
        print(f"[h2h] handyrl_tpu trained in {out['ours_train_s']}s", flush=True)
    if args.phase in ("all", "pit"):
        print(f"[h2h] pitting: {args.games} games, temperature "
              f"{args.temperature}, seat-balanced", flush=True)
        out["pit"] = pit(ref_dir, ours_dir, args.games, args.temperature)
        wp = out["pit"]["ours_win_points"]
        print(f"[h2h] handyrl_tpu win points vs reference: {wp:.3f} "
              f"over {out['pit']['games']} games", flush=True)

        stamp = datetime.datetime.now(datetime.timezone.utc).strftime("%Y-%m-%d_%H%M")
        dest = os.path.join(REPO, "docs", "captures",
                            f"head_to_head_{stamp}.json")
        with open(dest, "w") as f:
            json.dump(out, f, indent=2)
        print(f"[h2h] wrote {dest}", flush=True)


if __name__ == "__main__":
    main()
