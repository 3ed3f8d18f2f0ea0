"""Twin-less env compiler tests (envs/autovec.py).

Three parity layers pin the lift end to end:

1. rules == scalar env: the pure-numpy rules namespace, executed with
   host numpy, replays random games in lock-step with the 17-method host
   Environment (ConnectFour here; the device-rollout suite replays whole
   device-generated games through the host env on top of this);
2. lift == rules: ``verify()`` steps random games through the numpy
   rules and the lifted jnp env simultaneously (the
   ``autovec_verify_games`` startup self-check);
3. lift == hand twin: the autovectorized TicTacToe is bit-identical to
   the hand-written ``VectorTicTacToe`` on identical action streams —
   the apples-to-apples pair a lift is judged on (ROADMAP D9).

Plus the loud-diagnostic contract: every liftability break (in-place
mutation, value-dependent branching, missing jnp API, shape-unstable
apply, np.random) must fail at ``autovectorize`` time as an
``AutovecError`` naming the offending function.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from handyrl_tpu.envs.autovec import AutovecError, autovectorize
from handyrl_tpu.envs.tictactoe import TicTacToeRules
from handyrl_tpu.envs.vector_tictactoe import VectorTicTacToe

pytestmark = pytest.mark.league


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------


def test_connect_four_rules_match_scalar_env():
    """Layer 1: the numpy rules ARE the scalar env's rules — random games
    stepped through both in lock-step (turn view, legality, terminal,
    outcome)."""
    from examples.connect_four import ConnectFourRules as R
    from examples.connect_four import Environment

    rng = np.random.default_rng(3)
    for _ in range(25):
        env = Environment()
        env.reset()
        state = R.init()
        for step in range(R.max_steps):
            assert bool(R.terminal(state, step)) == env.terminal()
            if env.terminal():
                break
            legal = np.flatnonzero(np.asarray(R.legal_mask(state)))
            assert legal.tolist() == env.legal_actions()
            np.testing.assert_allclose(
                np.asarray(R.observation(state, step)),
                env.observation(env.turn()),
                atol=1e-6,
            )
            a = int(rng.choice(legal))
            state = R.apply(state, a, step)
            env.play(a)
        out = np.asarray(R.outcome(state))
        host = env.outcome()
        assert float(out[0]) == host[0] and float(out[1]) == host[1]


def test_verify_passes_for_bundled_rules():
    """Layer 2: the built-in rules namespaces clear their own step-parity
    self-check (what autovec_verify_games runs at Learner startup)."""
    from examples.connect_four import ConnectFourRules

    autovectorize(TicTacToeRules).verify(16, seed=0)
    autovectorize(ConnectFourRules).verify(16, seed=1)


def test_lift_bit_identical_to_hand_twin():
    """Layer 3: autovec TicTacToe vs the hand-written VectorTicTacToe,
    same action stream — every observable bit-equal at every step."""
    V = autovectorize(TicTacToeRules)
    assert (V.num_actions, V.max_steps, V.num_players) == (9, 9, 2)
    rng = np.random.default_rng(0)
    s_a, s_h = V.init(16), VectorTicTacToe.init(16)
    for t in range(V.max_steps):
        assert np.array_equal(
            jax.device_get(V.terminal(s_a, t)),
            jax.device_get(VectorTicTacToe.terminal(s_h, t)),
        )
        la = jax.device_get(V.legal_mask(s_a))
        assert np.array_equal(la, jax.device_get(VectorTicTacToe.legal_mask(s_h)))
        assert np.array_equal(
            jax.device_get(V.observation(s_a, t)),
            jax.device_get(VectorTicTacToe.observation(s_h, t)),
        )
        acts = np.asarray(
            [rng.choice(np.flatnonzero(m)) if m.any() else 0 for m in la],
            np.int32,
        )
        s_a = V.apply(s_a, jnp.asarray(acts), t)
        s_h = VectorTicTacToe.apply(s_h, jnp.asarray(acts), t)
    assert np.array_equal(
        jax.device_get(V.outcome(s_a)), jax.device_get(VectorTicTacToe.outcome(s_h))
    )


def test_lift_is_memoized_and_flagged():
    V = autovectorize(TicTacToeRules)
    assert autovectorize(TicTacToeRules) is V
    assert V.__autovec__ is True
    assert V.rules is TicTacToeRules


def test_example_env_vector_twin_is_the_lift():
    """The zoo's ConnectFour onboards the device path with NO hand
    twin: vector_env() must hand back the autovec lift."""
    from examples.connect_four import ConnectFourRules, Environment

    venv = Environment.vector_env()
    assert venv is autovectorize(ConnectFourRules)


# ---------------------------------------------------------------------------
# loud diagnostics
# ---------------------------------------------------------------------------


def _rules(**overrides):
    """A minimal liftable 2-action namespace, with injectable breakage."""

    class Minimal:
        num_actions = 2
        max_steps = 2
        num_players = 2

        @staticmethod
        def init():
            return {"x": np.zeros(2, np.int8)}

        @staticmethod
        def observation(state, step):
            return state["x"].astype(np.float32)

        @staticmethod
        def legal_mask(state):
            return state["x"] == 0

        @staticmethod
        def terminal(state, step):
            return (state["x"] != 0).all() | (step >= 2)

        @staticmethod
        def apply(state, action, step):
            x = np.where(np.arange(2) == action, np.int8(1), state["x"])
            return {"x": x}

        @staticmethod
        def outcome(state):
            return state["x"].astype(np.float32)

    for name, fn in overrides.items():
        setattr(Minimal, name, staticmethod(fn))
    Minimal.__name__ = "Minimal" + "_".join(overrides) if overrides else "Minimal"
    return Minimal


def test_minimal_rules_lift():
    autovectorize(_rules()).verify(4, seed=0)


def test_inplace_mutation_fails_loudly():
    def apply(state, action, step):
        x = state["x"].copy()
        x[action] = 1                      # in-place: not liftable
        return {"x": x}

    with pytest.raises(AutovecError, match=r"apply.*immutable|apply.*liftab"):
        autovectorize(_rules(apply=apply))


def test_value_dependent_branch_fails_loudly():
    def terminal(state, step):
        if state["x"][0] > 0:              # python branch on array value
            return np.bool_(True)
        return np.bool_(step >= 2)

    with pytest.raises(AutovecError, match="terminal"):
        autovectorize(_rules(terminal=terminal))


def test_missing_jnp_api_fails_loudly():
    def outcome(state):
        return np.busday_count("2026-01", "2026-02") * state["x"].astype(np.float32)

    with pytest.raises(AutovecError, match="busday_count"):
        autovectorize(_rules(outcome=outcome))


def test_np_random_fails_loudly():
    def apply(state, action, step):
        return {"x": (state["x"] + np.random.randint(2)).astype(np.int8)}

    with pytest.raises(AutovecError, match="np.random"):
        autovectorize(_rules(apply=apply))


def test_shape_unstable_apply_fails_loudly():
    def apply(state, action, step):
        return {"x": np.concatenate([state["x"], state["x"]])}

    with pytest.raises(AutovecError, match="shape/dtype-stable|changes state"):
        autovectorize(_rules(apply=apply))


def test_wrong_legal_mask_spec_fails_loudly():
    def legal_mask(state):
        return (state["x"] == 0).astype(np.float32)

    with pytest.raises(AutovecError, match="legal_mask"):
        autovectorize(_rules(legal_mask=legal_mask))


def test_missing_function_fails_loudly():
    bad = _rules()
    del bad.outcome
    with pytest.raises(AutovecError, match="outcome"):
        autovectorize(bad)


def test_totality_wrapper_freezes_finished_lanes():
    """Finished lanes must pass through apply unchanged (the
    vector_common select) even though the traced user apply still ran."""
    V = autovectorize(_rules())
    state = V.init(3)
    # lane 0 finishes at step 0+1 (both cells set? no — one action sets one
    # cell); drive lane 0 two steps so it terminates, then step again
    state = V.apply(state, jnp.asarray([0, 0, 1]), 0)
    state = V.apply(state, jnp.asarray([1, 0, 1]), 1)
    done = jax.device_get(V.terminal(state, 1))       # lane 0 only
    assert done.tolist() == [True, False, False]
    snap = jax.device_get(state["x"])
    state2 = V.apply(state, jnp.asarray([0, 0, 0]), 1)
    snap2 = jax.device_get(state2["x"])
    assert np.array_equal(snap2[done], snap[done])
    assert not np.array_equal(snap2[~done], snap[~done])
