"""On-device evaluation: batched net-vs-baseline matches in one jit.

The host evaluator (runtime/evaluation.py, reference evaluation.py:153-261)
plays one game per thread through per-step inference calls — on a 1-core
host it starves: both round-3 learning soaks recorded
NaN/sparse per-epoch win-rate curves because the single eval worker could
not finish games between epoch boundaries.  This module is the device twin
of that loop for vector envs: N lanes play the NET (greedy argmax, the
host Agent's temperature-0 behavior) on designated seats against a
scripted baseline on the others — ``rulebase`` via the env's
``rule_based_action_all`` device twin, or ``random`` via Gumbel-max over
the legal mask — with streaming auto-reset, emitting only (done, outcome)
per step.  The host aggregates exact outcome counts, so ``wp_func`` and
the soak margin calibration apply unchanged.

Seat balancing: ``net_seat`` assigns the net's seat PER LANE (round-robin
by default), the batched analogue of evaluate_mp's first/second patterns
(evaluation.py:216-219).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tree_map

ILLEGAL = 1e32

# XLA module name of the eval program (``jit_device_eval`` in a profile)
EVAL_PROGRAM = "device_eval"


def build_eval_stream_fn(venv, module, n_lanes: int, k_steps: int,
                         opponent: str = "rulebase", mesh=None):
    """Compile-once ``fn(params, state, hidden, net_seat, key) ->
    (state, hidden, record)``: scan ``k_steps`` game steps over
    ``n_lanes`` auto-resetting eval matches.

    ``net_seat`` is a (B,) int32 array: the seat the net plays in each
    lane (every other seat runs the baseline).  The record carries
    ``done`` (K, B) and ``outcome`` (K, B, P) — final scores where done,
    the same contract as the streaming rollout's record fields.
    """
    if opponent == "rulebase" and not hasattr(venv, "rule_based_action_all"):
        raise ValueError(
            f"{getattr(venv, '__name__', type(venv).__name__)} has no "
            "rule_based_action_all device twin; use opponent='random'"
        )
    if opponent not in ("rulebase", "random"):
        raise ValueError(f"device eval opponent must be rulebase|random, got {opponent!r}")
    P = venv.num_players

    def fn(params, state, hidden, net_seat, key):
        def body(carry, key_t):
            state, hidden = carry
            kr, ka, kf = jax.random.split(key_t, 3)
            reset = state["done"]
            state = venv.reset_done(state, kr)
            if hidden is not None:
                hidden = tree_map(
                    lambda h: h * ~reset.reshape((-1,) + (1,) * (h.ndim - 1)),
                    hidden,
                )
            obs = venv.observation(state)                # leaves (B, P, ...)
            B = state["done"].shape[0]
            flat = tree_map(lambda x: x.reshape((B * P,) + x.shape[2:]), obs)
            h_flat = (
                None if hidden is None
                else tree_map(lambda h: h.reshape((B * P,) + h.shape[2:]), hidden)
            )
            out = module.apply({"params": params}, flat, h_flat)
            if hidden is not None:
                # eval advances hidden for every seat every step, like the
                # host Agent with observation=True (agents.py observe())
                hidden = tree_map(
                    lambda h: h.reshape((B, P) + h.shape[1:]), out["hidden"]
                )
            logits = out["policy"].astype(jnp.float32).reshape(B, P, -1)
            legal = venv.legal_mask_all(state)           # (B, P, A)
            masked = jnp.where(legal, logits, logits - ILLEGAL)
            net_act = jnp.argmax(masked, axis=-1).astype(jnp.int32)  # greedy
            if opponent == "rulebase":
                opp_act = venv.rule_based_action_all(state, ka)
            else:
                g = jax.random.gumbel(ka, masked.shape)
                opp_act = jnp.argmax(
                    jnp.where(legal, g, -jnp.inf), axis=-1
                ).astype(jnp.int32)
            is_net = jnp.arange(P, dtype=jnp.int32)[None, :] == net_seat[:, None]
            actions = jnp.where(is_net, net_act, opp_act)
            state = venv.step(state, actions, kf)
            record = {
                "done": state["done"],
                "outcome": venv.outcome_scores(state),
            }
            return (state, hidden), record

        (state, hidden), records = jax.lax.scan(
            body, (state, hidden), jax.random.split(key, k_steps)
        )
        return state, hidden, records

    fn.__name__ = EVAL_PROGRAM
    if mesh is None:
        return jax.jit(fn, donate_argnums=(1, 2))
    from jax.sharding import NamedSharding, PartitionSpec

    lanes = NamedSharding(mesh, PartitionSpec("dp"))
    rec = NamedSharding(mesh, PartitionSpec(None, "dp"))
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        fn, donate_argnums=(1, 2),
        in_shardings=(rep, lanes, lanes, lanes, rep),
        out_shardings=(lanes, lanes, rec),
    )


class DeviceEvaluator:
    """Reusable evaluator: counts net-seat outcomes over >= num_games
    finished matches, reporting {outcome: count} like evaluate_mp's
    totals (so wp_func applies)."""

    def __init__(self, venv, module, n_lanes: int,
                 opponent: str = "rulebase", k_steps: int = 32, mesh=None):
        # fail at construction, not at the first evaluate() trace: the
        # eval stream drives the STREAMING contract; episodic twins
        # (VectorTicTacToe-style) don't have it
        if not (hasattr(venv, "reset_done") and hasattr(venv, "step")):
            raise ValueError(
                f"DeviceEvaluator needs a streaming vector env "
                f"(reset_done/step hooks); "
                f"{getattr(venv, '__name__', type(venv).__name__)} is "
                "episodic — use host eval workers for this env"
            )
        self.venv = venv
        self.module = module
        self.n_lanes = n_lanes
        self.opponent = opponent
        # a size-1 mesh gets no sharding, but the dispatch locks must
        # still cover only ITS device: locking all local devices (the
        # None legacy scope) would stall a split actor plane for the
        # whole multi-dispatch eval at every epoch boundary
        self.mesh = mesh if mesh is not None and mesh.size > 1 else None
        self._lock_devices = (
            list(mesh.devices.flat) if mesh is not None else None
        )
        self._fn = build_eval_stream_fn(
            venv, module, n_lanes, k_steps, opponent=opponent, mesh=self.mesh,
        )
        # per-lane net seat, round-robin: the batched first/second balance
        self._net_seat = jnp.arange(n_lanes, dtype=jnp.int32) % venv.num_players
        self._net_seat_host = np.asarray(self._net_seat)

    def evaluate(self, params, num_games: int, key,
                 max_calls: int = 64) -> Dict[float, int]:
        """Play until ``num_games`` matches finish (or ``max_calls``
        dispatches); returns exact outcome counts for the net's seat."""
        from ..parallel.mesh import dispatch_serialized

        venv = self.venv
        key, k0 = jax.random.split(key)
        state = venv.init(self.n_lanes, k0)
        hidden = self.module.initial_state((self.n_lanes, venv.num_players))
        net_seat = self._net_seat
        seat = self._net_seat_host
        counts: Dict[float, int] = {}
        games = 0
        for _ in range(max_calls):
            key, sub = jax.random.split(key)
            state, hidden, rec = dispatch_serialized(
                lambda: self._fn(params, state, hidden, net_seat, sub),
                self._lock_devices,
            )
            # graftlint: allow[HS001] reason=epoch-boundary eval consumes (done, outcome) on host by design; this loop runs between epochs, not in the training hot loop
            done = np.asarray(jax.device_get(rec["done"]))       # (K, B)
            # graftlint: allow[HS001] reason=epoch-boundary eval consumes (done, outcome) on host by design; this loop runs between epochs, not in the training hot loop
            outcome = np.asarray(jax.device_get(rec["outcome"]))  # (K, B, P)
            ks, bs = np.nonzero(done)
            for k, b in zip(ks, bs):
                o = float(outcome[k, b, seat[b]])
                counts[o] = counts.get(o, 0) + 1
                games += 1
            if games >= num_games:
                break
        return counts
