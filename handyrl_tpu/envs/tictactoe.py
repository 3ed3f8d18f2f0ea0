"""Tic-Tac-Toe — 2-player turn-based zero-sum game.

Behavioral parity with reference handyrl/envs/tictactoe.py:72-168 (same
action encoding 0..8 = row*3+col, same 'A1'-style strings, same 3x3x3
observation planes) but implemented on a flat 9-cell board with a
precomputed win-line table instead of per-move row/col/diag sums.
The net lives in handyrl_tpu/models (SimpleConvNet), not here.
"""

from __future__ import annotations

import random

import numpy as np

from .base import BaseEnvironment

# All 8 winning index triples on the flat board.
WIN_LINES = np.array(
    [
        [0, 1, 2], [3, 4, 5], [6, 7, 8],  # rows
        [0, 3, 6], [1, 4, 7], [2, 5, 8],  # cols
        [0, 4, 8], [2, 4, 6],             # diagonals
    ],
    dtype=np.int64,
)

ROWS, COLS = "ABC", "123"


class Environment(BaseEnvironment):
    BLACK, WHITE = 1, -1
    _GLYPH = {0: "_", 1: "O", -1: "X"}

    def __init__(self, args=None):
        super().__init__(args)
        self.reset()

    def reset(self, args=None):
        self.cells = np.zeros(9, dtype=np.int8)
        self.to_move = self.BLACK
        self.winner = 0  # +1 black, -1 white, 0 none
        self.history: list[int] = []

    # -- codecs -------------------------------------------------------------

    def action2str(self, a, player=None):
        return ROWS[a // 3] + COLS[a % 3]

    def str2action(self, s, player=None):
        return ROWS.index(s[0]) * 3 + COLS.index(s[1])

    def __str__(self):
        grid = self.cells.reshape(3, 3)
        lines = ["  " + " ".join(COLS)]
        for r in range(3):
            lines.append(ROWS[r] + " " + " ".join(self._GLYPH[int(v)] for v in grid[r]))
        lines.append("record = " + " ".join(self.action2str(a) for a in self.history))
        return "\n".join(lines)

    # -- transitions --------------------------------------------------------

    def play(self, action, player=None):
        self.cells[action] = self.to_move
        if any(self.cells[line].sum() == 3 * self.to_move for line in WIN_LINES[self._lines_through(action)]):
            self.winner = self.to_move
        self.to_move = -self.to_move
        self.history.append(action)

    @staticmethod
    def _lines_through(action):
        return [i for i, line in enumerate(WIN_LINES) if action in line]

    # -- replica sync -------------------------------------------------------

    def diff_info(self, player=None):
        return self.action2str(self.history[-1]) if self.history else ""

    def update(self, info, reset):
        if reset:
            self.reset()
        else:
            self.play(self.str2action(info))

    # -- game state ---------------------------------------------------------

    def turn(self):
        return len(self.history) % 2

    def terminal(self):
        return self.winner != 0 or len(self.history) == 9

    def outcome(self):
        score = {0: 0, 1: 0}
        if self.winner == self.BLACK:
            score = {0: 1, 1: -1}
        elif self.winner == self.WHITE:
            score = {0: -1, 1: 1}
        return score

    def legal_actions(self, player=None):
        return np.flatnonzero(self.cells == 0).tolist()

    def players(self):
        return [0, 1]

    @staticmethod
    def vector_env():
        """Device-resident twin (pure jnp transitions) for fully on-device
        self-play (runtime/device_rollout.py)."""
        from .vector_tictactoe import VectorTicTacToe

        return VectorTicTacToe

    def observation(self, player=None):
        """3 planes (C, 3, 3): [is-my-turn-view, my stones, opponent stones]."""
        my_view = player is None or player == self.turn()
        me = self.to_move if my_view else -self.to_move
        grid = self.cells.reshape(3, 3)
        return np.stack(
            [
                np.full((3, 3), 1.0 if my_view else 0.0),
                grid == me,
                grid == -me,
            ]
        ).astype(np.float32)

    def action_size(self):
        return 9

    def default_net(self):
        from ..models import SimpleConvNet

        return SimpleConvNet()


class TicTacToeRules:
    """Pure single-game numpy rules to the autovec liftability contract
    (envs/autovec.py) — the same rules as ``Environment`` and the
    hand-written ``VectorTicTacToe`` twin.

    This namespace exists as the apples-to-apples yardstick for the
    twin-less path: lifted with ``autovectorize`` it runs beside the
    hand-written ``vector_tictactoe.VectorTicTacToe`` — same game, same
    net, so a comparison isolates the cost of the lift itself (ROADMAP D9).
    Bit-parity of every observable against the hand twin is pinned by
    tests/test_autovec.py.

    State (one game): ``cells`` (9,) int8, ``winner`` () int8.
    """

    num_actions = 9
    max_steps = 9
    num_players = 2

    @staticmethod
    def _color(step: int) -> int:
        return 1 if step % 2 == 0 else -1

    @staticmethod
    def init():
        return {
            "cells": np.zeros(9, np.int8),
            "winner": np.zeros((), np.int8),
        }

    @staticmethod
    def observation(state, step: int):
        """(3, 3, 3) planes for the turn player — identical to
        ``VectorTicTacToe.observation``: [my-view ones, my stones,
        opponent stones]."""
        me = TicTacToeRules._color(step)
        grid = state["cells"].reshape(3, 3)
        return np.stack(
            [
                np.ones((3, 3), np.float32),
                (grid == me).astype(np.float32),
                (grid == -me).astype(np.float32),
            ]
        )

    @staticmethod
    def legal_mask(state):
        return state["cells"] == 0

    @staticmethod
    def terminal(state, step: int):
        return (state["winner"] != 0) | (step >= 9)

    @staticmethod
    def apply(state, action, step: int):
        me = TicTacToeRules._color(step)
        cells = np.where(np.arange(9) == action, np.int8(me), state["cells"])
        lines = cells[WIN_LINES]                              # (8, 3)
        won = (lines.sum(axis=-1) == 3 * me).any()
        winner = np.where(won, np.int8(me), state["winner"]).astype(np.int8)
        return {"cells": cells, "winner": winner}

    @staticmethod
    def outcome(state):
        w = state["winner"].astype(np.float32)
        return np.stack([w, -w])


if __name__ == "__main__":
    e = Environment()
    for _ in range(10):
        e.reset()
        while not e.terminal():
            e.play(random.choice(e.legal_actions()))
        print(e)
        print(e.outcome())
