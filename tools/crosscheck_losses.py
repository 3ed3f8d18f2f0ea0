"""Numerical parity check of target algorithms vs the reference torch code.

Feeds identical random tensors through reference handyrl.losses.compute_target
and handyrl_tpu.ops.targets.compute_target; asserts outputs match to float32
tolerance for every algorithm / gamma / lambda / reward combination.
Dev/judging aid only (needs torch + mounted reference).
"""

import os
import sys

# this tool mixes torch and jax in one process: pin jax to CPU before it
# is imported and keep both runtimes to one OpenMP thread each
# (oversubscribed OpenMP pools from the two runtimes deadlock)
os.environ.setdefault("OMP_NUM_THREADS", "1")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

sys.path.insert(0, "/root/repo")
sys.path.insert(0, "/root/reference")

import jax  # noqa: E402

import torch  # noqa: E402

torch.set_num_threads(1)

from handyrl.losses import compute_target as ref_compute_target  # noqa: E402
from handyrl_tpu.ops.targets import compute_target as tpu_compute_target  # noqa: E402


def main():
    rng = np.random.default_rng(7)
    B, T, P, C = 3, 8, 2, 1
    checked = 0
    for algo in ["MC", "TD", "UPGO", "VTRACE"]:
        for gamma in [1.0, 0.8]:
            for lmb in [0.7, 1.0, 0.0]:
                for with_rewards in [True, False]:
                    values = rng.normal(size=(B, T, P, C)).astype(np.float32)
                    returns = rng.normal(size=(B, T, P, C)).astype(np.float32)
                    rewards = rng.normal(size=(B, T, P, C)).astype(np.float32) if with_rewards else None
                    rhos = rng.uniform(0, 1.5, size=(B, T, P, C)).astype(np.float32)
                    cs = rng.uniform(0, 1.5, size=(B, T, P, C)).astype(np.float32)
                    masks = (rng.uniform(size=(B, T, P, C)) > 0.3).astype(np.float32)

                    t_rew = torch.from_numpy(rewards) if rewards is not None else None
                    ref_tgt, ref_adv = ref_compute_target(
                        algo, torch.from_numpy(values), torch.from_numpy(returns), t_rew,
                        lmb, gamma, torch.from_numpy(rhos), torch.from_numpy(cs), torch.from_numpy(masks),
                    )
                    tgt, adv = tpu_compute_target(algo, values, returns, rewards, lmb, gamma, rhos, cs, masks)
                    np.testing.assert_allclose(np.asarray(tgt), ref_tgt.numpy(), rtol=2e-5, atol=1e-5,
                                               err_msg=f"{algo} g={gamma} l={lmb} rew={with_rewards} target")
                    np.testing.assert_allclose(np.asarray(adv), ref_adv.numpy(), rtol=2e-5, atol=1e-5,
                                               err_msg=f"{algo} g={gamma} l={lmb} rew={with_rewards} advantage")
                    checked += 1
    print(f"targets parity: {checked} configurations identical vs reference torch implementation")


if __name__ == "__main__":
    main()
