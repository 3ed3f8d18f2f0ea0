"""Environment contract tests.

Same three-interface strategy as the reference (tests/test_environment.py):
property checks, full random games through the shared-env interface, and
full games driven purely through the ``diff_info``/``update`` replica
protocol (the socket-free surrogate for network battle mode), plus extra
determinism/outcome invariants the reference lacks.
"""

import random

import numpy as np
import pytest

from handyrl_tpu.envs import make_env

ENV_NAMES = [
    "TicTacToe",
    "ParallelTicTacToe",
    "Geister",
    "HungryGeese",
    # first-class zoo entry for the worked example (the league and
    # autovec tests run against it as a registered scenario)
    "ConnectFour",
    # ...and the same module by dotted path, exercising the registry
    # fallback the way a user would (docs/custom_environment.md)
    "examples.connect_four",
]


def test_connect_four_registry_entry_is_the_example_module():
    """`env: ConnectFour` must resolve to the same Environment class as
    the documented dotted path — one module, two spellings."""
    a = make_env({"env": "ConnectFour"})
    b = make_env({"env": "examples.connect_four"})
    assert type(a) is type(b)


def _make(name):
    return make_env({"env": name})


@pytest.mark.parametrize("name", ENV_NAMES)
def test_environment_property(name):
    e = _make(name)
    players = e.players()
    assert len(players) >= 2
    str(e)
    e.reset()
    for p in e.turns():
        acts = e.legal_actions(p)
        assert len(acts) > 0
        # codecs round-trip
        for a in acts[:5]:
            assert e.str2action(e.action2str(a, p), p) == a


@pytest.mark.parametrize("name", ENV_NAMES)
def test_environment_local(name):
    random.seed(0)
    e = _make(name)
    for _ in range(100):
        e.reset()
        steps = 0
        while not e.terminal():
            actions = {p: random.choice(e.legal_actions(p)) for p in e.turns()}
            e.step(actions)
            e.reward()
            steps += 1
            assert steps < 1000, "game failed to terminate"
        outcome = e.outcome()
        assert set(outcome.keys()) == set(e.players())
        # zero-sum style outcomes
        assert abs(sum(outcome.values())) < 1e-6


@pytest.mark.parametrize("name", ENV_NAMES)
def test_environment_network(name):
    """Replica envs driven only by diff_info/update stay action-consistent."""
    random.seed(1)
    e = _make(name)
    replicas = {p: _make(name) for p in e.players()}
    for _ in range(100):
        e.reset()
        for p, rep in replicas.items():
            rep.update(e.diff_info(p), True)
        while not e.terminal():
            actions = {}
            for p in e.turns():
                assert set(e.legal_actions(p)) == set(replicas[p].legal_actions(p))
                # a replica must see exactly what the master would show it
                np.testing.assert_equal(replicas[p].observation(p), e.observation(p))
                a = random.choice(replicas[p].legal_actions(p))
                actions[p] = e.str2action(replicas[p].action2str(a, p), p)
            e.step(actions)
            for p, rep in replicas.items():
                rep.update(e.diff_info(p), False)
                # replicas must agree the game is (not) over
                assert rep.terminal() == e.terminal()
            e.reward()
        e.outcome()


@pytest.mark.parametrize("name", ENV_NAMES)
def test_observation_shape_stable(name):
    """Observations keep identical pytree structure/shape/dtype every step —
    a hard requirement for fixed-shape XLA batching."""
    import jax

    random.seed(2)
    e = _make(name)
    e.reset()
    ref_struct = jax.tree.map(lambda x: (x.shape, x.dtype), e.observation(e.players()[0]))
    for _ in range(3):
        e.reset()
        while not e.terminal():
            for p in e.players():
                struct = jax.tree.map(lambda x: (x.shape, x.dtype), e.observation(p))
                assert struct == ref_struct
            e.step({p: random.choice(e.legal_actions(p)) for p in e.turns()})


def test_tictactoe_known_positions():
    e = _make("TicTacToe")
    e.reset()
    # O plays 0,1,2 (top row) while X plays 3,4: O wins
    for a in [0, 3, 1, 4, 2]:
        e.play(a)
    assert e.terminal()
    assert e.outcome() == {0: 1, 1: -1}
    # X wins the middle column: O plays 0,2,6 / X plays 1,4,7
    e.reset()
    for a in [0, 1, 2, 4, 6, 7]:
        e.play(a)
    assert e.terminal()
    assert e.outcome() == {0: -1, 1: 1}
    # full-board draw: 0,1,2,4,3,5,7,6,8 alternating
    e.reset()
    for a in [0, 1, 2, 4, 3, 5, 7, 6, 8]:
        e.play(a)
    assert e.terminal()
    assert e.outcome() == {0: 0, 1: 0}


def test_geister_piece_accounting():
    random.seed(3)
    e = _make("Geister")
    for _ in range(20):
        e.reset()
        while not e.terminal():
            e.play(random.choice(e.legal_actions()))
            counts = e._piece_counts()
            total = sum(counts[0]) + sum(counts[1])
            assert total == int(e.alive.sum()) <= 16
        assert e.win_color in (0, 1, 2)


def test_hungry_geese_ranking():
    e = _make("HungryGeese")
    e.reset()
    e.rank_rewards = [400, 400, 300, 100]
    out = e.outcome()
    assert out[0] == out[1] > out[2] > out[3]
    assert abs(sum(out.values())) < 1e-9


class TestHungryGeeseRules:
    """Pin every official-interpreter rule from docs/hungry_geese_parity.md
    (kaggle_environments is not installable here, so each rule is pinned by
    a constructed position instead of a lock-step trace)."""

    def _env(self):
        e = _make("HungryGeese")
        e.reset()
        return e

    @staticmethod
    def _cell(r, c):
        return r * 11 + c

    def _setup(self, e, geese, food):
        e.geese = [list(g) for g in geese]
        e.active = [bool(g) for g in geese]
        e.food = list(food)
        e.last_actions = {}
        e.step_count = 0

    def test_reverse_death(self):
        e = self._env()
        self._setup(e, [[self._cell(3, 3)], [self._cell(0, 0)], [], []], [self._cell(6, 10)])
        e.last_actions = {0: 0}  # last moved NORTH
        e.step({0: 1, 1: 0})  # 0 reverses SOUTH -> dies
        assert not e.active[0] and e.geese[0] == []

    def test_food_growth_keeps_tail(self):
        e = self._env()
        head, tail = self._cell(3, 3), self._cell(3, 2)
        food = self._cell(2, 3)  # north of head
        self._setup(e, [[head, tail], [self._cell(6, 0)], [], []], [food, self._cell(6, 10)])
        e.step({0: 0, 1: 0})  # NORTH onto food
        assert e.geese[0] == [food, head, tail]  # grew, tail kept
        assert food not in e.food

    def test_move_without_food_pops_tail(self):
        e = self._env()
        head, tail = self._cell(3, 3), self._cell(3, 2)
        self._setup(e, [[head, tail], [self._cell(6, 0)], [], []], [self._cell(6, 10)])
        e.step({0: 0, 1: 0})
        assert e.geese[0] == [self._cell(2, 3), head]

    def test_chasing_own_tail_is_legal(self):
        """Rule 3: tail pops before the self-collision check, so moving into
        the current tail cell (not eating) is legal."""
        e = self._env()
        # 2x2 loop: head at (3,3), body (3,4), (4,4), tail (4,3); EAST... use
        # square ring and move head onto the vacating tail cell
        ring = [self._cell(3, 3), self._cell(3, 4), self._cell(4, 4), self._cell(4, 3)]
        self._setup(e, [ring, [self._cell(0, 0)], [], []], [self._cell(6, 10)])
        e.step({0: 1, 1: 0})  # SOUTH onto (4,3) = current tail
        assert e.active[0]
        assert e.geese[0] == [self._cell(4, 3), self._cell(3, 3), self._cell(3, 4), self._cell(4, 4)]

    def test_self_collision_death(self):
        e = self._env()
        # long body: moving EAST hits own body cell that does NOT vacate
        g = [self._cell(3, 3), self._cell(2, 3), self._cell(2, 4), self._cell(3, 4), self._cell(4, 4), self._cell(4, 3)]
        self._setup(e, [g, [self._cell(0, 0)], [], []], [self._cell(6, 10)])
        e.step({0: 3, 1: 0})  # EAST into (3,4)
        assert not e.active[0]

    def test_hunger_pops_tail_on_step_40(self):
        e = self._env()
        head, tail = self._cell(3, 3), self._cell(3, 2)
        self._setup(e, [[head, tail], [self._cell(6, 0)], [], []], [self._cell(6, 10)])
        e.step_count = 39  # this step becomes 40
        e.step({0: 0, 1: 0})
        assert len(e.geese[0]) == 1  # moved (pop) + hunger (pop) from 2+head

    def test_hunger_starves_length_one(self):
        e = self._env()
        self._setup(e, [[self._cell(3, 3)], [self._cell(6, 0), self._cell(6, 1)], [], []], [self._cell(0, 5)])
        e.step_count = 39
        e.step({0: 0, 1: 0})
        assert e.geese[0] == []  # shrank to zero
        assert e.geese[1]        # survived (game then ends: last goose standing)
        assert e.terminal()

    def test_head_to_head_collision_kills_both(self):
        e = self._env()
        a, b = self._cell(3, 3), self._cell(3, 5)
        self._setup(e, [[a], [b], [self._cell(0, 0)], []], [self._cell(6, 10)])
        e.step({0: 3, 1: 2, 2: 0})  # both into (3,4)
        assert e.geese[0] == [] and e.geese[1] == []
        assert e.geese[2]  # last goose standing; game ends
        assert e.terminal()

    def test_head_into_body_kills_mover_only(self):
        e = self._env()
        mover = [self._cell(3, 3)]
        wall = [self._cell(2, 4), self._cell(2, 3), self._cell(2, 2)]
        # wall moves SOUTH to (3,4); mover EAST to (3,4)? that's head-to-head.
        # Instead: mover NORTH into wall's mid-body cell (2,3) which stays.
        self._setup(e, [mover, wall, [self._cell(6, 0)], []], [self._cell(6, 10)])
        e.step({0: 0, 1: 1, 2: 0})  # wall head (2,4) SOUTH to (3,4)
        assert not e.active[0]
        assert e.active[1]

    def test_shared_food_lower_index_eats_both_die(self):
        e = self._env()
        food = self._cell(3, 4)
        self._setup(e, [[self._cell(3, 3)], [self._cell(3, 5)], [self._cell(0, 0)], []], [food, self._cell(6, 10)])
        e.step({0: 3, 1: 2, 2: 0})
        assert food not in e.food  # removed exactly once
        assert not e.active[0] and not e.active[1]

    def test_dead_goose_keeps_previous_reward(self):
        """Rule 9: rewards update only for survivors, after deaths."""
        e = self._env()
        self._setup(e, [[self._cell(3, 3)], [self._cell(0, 0)], [self._cell(6, 5)], []], [self._cell(6, 10)])
        e.rank_rewards = [101, 101, 101, 101]
        e.last_actions = {0: 0}
        e.step({0: 1, 1: 0, 2: 0})  # goose 0 reverses and dies
        assert e.rank_rewards[0] == 101          # frozen at pre-death value
        assert e.rank_rewards[1] == 2 * 100 + 1  # (t+1)*scale + len
        # survival beats the dead goose in the final ranking
        assert e.rank_rewards[1] > e.rank_rewards[0]

    def test_food_respawns_to_min(self):
        e = self._env()
        self._setup(e, [[self._cell(3, 3)], [self._cell(0, 0)], [], []], [self._cell(3, 4)])
        e.step({0: 3, 1: 0})  # eat the only food
        assert len(e.food) == 2  # respawned to MIN_FOOD
        occupied = {c for g in e.geese for c in g}
        assert not (set(e.food) & occupied)

    def test_episode_step_limit(self):
        e = self._env()
        self._setup(e, [[self._cell(0, 0)], [self._cell(3, 3)], [self._cell(5, 5)], []], [self._cell(6, 10)])
        e.step_count = 198
        e.step({0: 0, 1: 0, 2: 0})
        assert e.terminal()  # 199 transitions = kaggle episodeSteps 200


def test_observation_viewpoint_rotation():
    """Geister: White's observation is the 180-rotation of the board."""
    random.seed(4)
    e = _make("Geister")
    e.reset()
    e.play(144)  # black layout 0
    e.play(144)  # white layout 0
    obs_b = e.observation(0)
    obs_w = e.observation(1)
    assert obs_b["board"].shape == (7, 6, 6)
    assert obs_w["board"].shape == (7, 6, 6)
    # plane 1 is "my pieces": white's own pieces rotated must equal black's view of white pieces
    np.testing.assert_allclose(
        np.rot90(obs_w["board"][1], k=2, axes=(0, 1)), obs_b["board"][2]
    )
