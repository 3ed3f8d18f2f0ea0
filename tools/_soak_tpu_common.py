"""Shared driver for the on-chip learning soaks (tools/soak_*_tpu.py).

Phase ``train`` (real chip, single process, clean exit): Learner.run() with
a device-replay config, artifacts in ``run_dir`` (metrics.jsonl +
models/latest.ckpt), then a CPU-pinned ``eval`` subprocess whose verdict —
not just its survival — becomes the process exit code.
Phase ``eval`` (CPU-pinned by JAX_PLATFORMS in its environment): matched offline evals of the trained net and
the SAME net untrained, each vs the baseline opponent through the shared
margin-calibrated aggregation (runtime/evaluation.py:eval_vs_baseline);
exits non-zero when the outcome margin misses the bar, so a no-learning
run can never read as a clean exit.
"""

import json
import os
import subprocess
import sys


def run(argv, script_path: str, cfg: dict, run_dir: str, opponent: str,
        margin: float, wp_bar: float, num_games: int = 240) -> None:
    mode = argv[1] if len(argv) > 1 else "train"
    if mode == "train":
        _train(script_path, cfg, run_dir)
    elif mode == "eval":
        _evaluate(cfg, run_dir, opponent, margin, wp_bar, num_games)
    else:
        raise SystemExit(f"unknown mode {mode!r} (train|eval)")


def _train(script_path: str, cfg: dict, run_dir: str) -> None:
    os.makedirs(run_dir, exist_ok=True)
    os.chdir(run_dir)
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.runtime.learner import Learner

    import jax
    d = jax.devices()[0]
    print(f"platform: {d.platform}:{getattr(d, 'device_kind', '?')}", flush=True)
    Learner(normalize_args(cfg)).run()
    print("training done; launching CPU-pinned matched eval", flush=True)
    # this process still holds the chip, so the eval child is pinned to
    # the CPU by its environment; its verdict is the run's whole point, so
    # its exit code (crash OR missed margin) is ours
    rc = subprocess.run([sys.executable, script_path, "eval"],
                        env=dict(os.environ, JAX_PLATFORMS="cpu"),
                        check=False).returncode
    if rc != 0:
        print(f"matched eval FAILED (rc={rc})", flush=True)
    sys.exit(rc)


def _evaluate(cfg: dict, run_dir: str, opponent: str, margin: float,
              wp_bar: float, num_games: int) -> None:
    from handyrl_tpu.agents import Agent
    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables
    from handyrl_tpu.runtime.evaluation import eval_vs_baseline, load_model_agent

    args = normalize_args(cfg)
    env_args = args["env_args"]
    env = make_env(env_args)
    module = env.net()

    untrained = Agent(InferenceModel(module, init_variables(module, env)))
    trained = load_model_agent(os.path.join(run_dir, "models", "latest.ckpt"),
                               env, module)
    wp_u, out_u = eval_vs_baseline(env_args, untrained, opponent, num_games)
    print(f"untrained vs {opponent}: wp {wp_u:.3f} mean outcome {out_u:.3f}",
          flush=True)
    wp_t, out_t = eval_vs_baseline(env_args, trained, opponent, num_games)
    print(f"trained   vs {opponent}: wp {wp_t:.3f} mean outcome {out_t:.3f}",
          flush=True)
    verdict = {
        "wp_untrained": wp_u, "wp_trained": wp_t,
        "outcome_untrained": out_u, "outcome_trained": out_t,
        "margin": out_t - out_u,
        "learns": bool(out_t > out_u + margin),
        "clears_wp_bar": bool(wp_t >= wp_bar),
    }
    print("RESULT " + json.dumps(verdict), flush=True)
    sys.exit(0 if verdict["learns"] and verdict["clears_wp_bar"] else 1)
