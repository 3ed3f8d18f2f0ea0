"""Measure the REFERENCE's generation strategy on HungryGeese, on this host.

BASELINE.md's 1,557 env-steps/s generation row is TicTacToe (tiny net,
9-step episodes); a HungryGeese rate divided by it makes the host actor
plane look several times slower than the reference than it is.  This
tool produces the like-for-like number: the reference's generation loop shape — ONE
batch-1 torch inference per ACTIVE player per step, single process
(reference generation.py:20-93 driving ModelWrapper model.py:50-60) —
using the reference's OWN torch GeeseNet (imported from
/root/reference/handyrl/envs/kaggle/hungry_geese.py with the missing
kaggle_environments dependency stubbed; the net class itself has no
kaggle dependency), stepping the same 7x11 torus rules.

Recorded in BASELINE.md.

Usage: python tools/reference_geese_gen.py [seconds]
"""

from __future__ import annotations

import sys
import time
import types

import numpy as np

sys.path.insert(0, "/root/repo")


def load_reference_geesenet():
    """Import the reference's torch GeeseNet without kaggle_environments:
    the module imports `make` at top level but only calls it inside
    Environment.__init__, which this tool never constructs."""
    sys.path.insert(0, "/root/reference")
    if "kaggle_environments" not in sys.modules:
        stub = types.ModuleType("kaggle_environments")

        def _unavailable(*_a, **_k):
            raise RuntimeError("kaggle_environments is not installed")

        stub.make = _unavailable
        sys.modules["kaggle_environments"] = stub
    import handyrl.envs.kaggle.hungry_geese as ref_hg

    return ref_hg.GeeseNet().eval()


def measure(duration: float = 10.0, seed: int = 0) -> float:
    import torch

    torch.set_num_threads(1)  # parity with the 1-core CI host

    from handyrl_tpu.envs import make_env

    np.random.seed(seed)
    env = make_env({"env": "HungryGeese"})
    net = load_reference_geesenet()

    steps = episodes = 0
    t0 = time.perf_counter()
    with torch.no_grad():
        while time.perf_counter() - t0 < duration:
            env.reset()
            while not env.terminal():
                actions = {}
                for p in env.turns():
                    obs = torch.from_numpy(env.observation(p))[None]
                    out = net(obs)
                    logits = out["policy"] if isinstance(out, dict) else out[0]
                    prob = torch.softmax(logits, -1).numpy().ravel()
                    actions[p] = int(np.random.choice(4, p=prob / prob.sum()))
                env.step(actions)
                steps += 1
            episodes += 1
    dt = time.perf_counter() - t0
    rate = steps / dt
    print(
        f"reference-style geese generation: {rate:.1f} env-steps/s "
        f"({episodes} episodes over {dt:.1f}s, torch 1-thread, batch-1/player)"
    )
    return rate


if __name__ == "__main__":
    measure(float(sys.argv[1]) if len(sys.argv) > 1 else 10.0)
