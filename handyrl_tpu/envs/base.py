"""Game environment protocol.

Mirrors the reference contract (handyrl/environment.py:41-145): the same 17
methods, so any HandyRL-style environment ports over directly.  Two
deliberate differences:

* Game logic here is pure numpy/python — environments never import a
  neural-net framework.  ``net()`` returns a Flax module (from
  ``handyrl_tpu.models``), loaded lazily.
* ``Environment`` subclasses may expose ``observation_spec()`` /
  ``action_size()`` so the runtime can pre-build fixed-shape device
  buffers without resetting a throwaway env.
"""

from __future__ import annotations

from typing import Any, Dict, List

from ..utils.trace import trace_phase


class BaseEnvironment:
    """Abstract game interface.

    Shapes of the game loop (see runtime/generation.py):
        reset() -> while not terminal(): turns()/observers() -> observation(p)
        -> legal_actions(p) -> step({player: action}) -> reward() ... outcome()

    Network-battle / replica synchronisation uses ``diff_info``/``update``:
    a master env emits a per-player delta after every transition, replica
    envs apply it and must stay consistent (legal-action sets identical).
    """

    def __init__(self, args: Dict[str, Any] | None = None):
        self.args: Dict[str, Any] = dict(args or {})

    def __str__(self) -> str:
        return ""

    # -- core transitions ---------------------------------------------------

    def reset(self, args: Dict[str, Any] | None = None):
        """Start a new game. Return a truthy value on unrecoverable error."""
        raise NotImplementedError()

    def play(self, action: int, player: int | None = None):
        """Apply a single player's action (turn-based games)."""
        raise NotImplementedError()

    def step(self, actions: Dict[int, int | None]):
        """Apply a joint action dict. Default: sequentially play non-None actions."""
        for player, action in actions.items():
            if action is not None:
                self.play(action, player)

    # -- whose move ---------------------------------------------------------

    def turn(self) -> int:
        """Turn player (single-actor games)."""
        return 0

    def turns(self) -> List[int]:
        """Players who act this step (simultaneous games override)."""
        return [self.turn()]

    def observers(self) -> List[int]:
        """Non-acting players who should still observe (e.g. to feed RNNs)."""
        return []

    # -- termination & rewards ---------------------------------------------

    def terminal(self) -> bool:
        raise NotImplementedError()

    def reward(self) -> Dict[int, float]:
        """Immediate rewards after the last step ({} if none)."""
        return {}

    def outcome(self) -> Dict[int, float]:
        """Final outcome per player at a terminal state."""
        raise NotImplementedError()

    # -- actions & players --------------------------------------------------

    def legal_actions(self, player: int | None = None) -> List[int]:
        raise NotImplementedError()

    def players(self) -> List[int]:
        return [0]

    def observation(self, player: int | None = None):
        """Numpy feature pytree for ``player``'s point of view."""
        raise NotImplementedError()

    # -- string codecs (used by match records & network battles) -----------

    def action2str(self, a: int, player: int | None = None) -> str:
        return str(a)

    def str2action(self, s: str, player: int | None = None) -> int:
        return int(s)

    # -- replica synchronisation (network battle mode) ----------------------

    def diff_info(self, player: int | None = None):
        return ""

    def update(self, info, reset: bool):
        raise NotImplementedError()

    # -- model factory ------------------------------------------------------

    _net_phase_done = False     # the process's first ``net()`` is a phase

    def net(self):
        """The Flax module for this game: ``_net()``, the first call of a
        process under the phase ``setup.net`` (it imports ``models``, and flax
        with it)."""
        if BaseEnvironment._net_phase_done:
            return self._net()
        BaseEnvironment._net_phase_done = True
        with trace_phase("setup.net", net=str(self.args.get("net") or "default")):
            return self._net()

    def _net(self):
        """Return the Flax module for this game (policy/value net).

        Honors ``env_args['net'] == 'transformer'`` (and ``'hybrid'``, the
        layer-pattern family of models/hybrid.py) for every environment:
        the generic KV-cache memory family (models/transformer.py) sized by
        ``transformer_spec()``, with ``env_args['net_args']`` merged over
        the spec — so configs can scale the family (d_model, n_layers,
        n_heads, memory_len, mlp_ratio) without a new env subclass.
        Environments implement ``default_net()`` for their bespoke
        architecture.
        """
        if self.args.get("net") == "transformer":
            from ..models import TransformerNet

            spec = dict(self.transformer_spec())
            spec.update(self.args.get("net_args") or {})
            return TransformerNet(**spec)
        if self.args.get("net") == "hybrid":
            # the layer-pattern family (models/hybrid.py): the same head
            # sizes, ``net_args`` carrying the pattern and the widths
            from ..models import HybridNet

            spec = {k: v for k, v in self.transformer_spec().items()
                    if k in ("num_actions", "with_return")}
            spec.update(self.args.get("net_args") or {})
            return HybridNet(**spec)
        return self.default_net()

    def default_net(self):
        """The environment's bespoke policy/value module."""
        raise NotImplementedError()

    def transformer_spec(self) -> Dict[str, Any]:
        """Constructor kwargs for the generic TransformerNet family."""
        return {"num_actions": self.action_size()}

    def action_size(self) -> int:
        """Total policy-head size (maximum action index + 1)."""
        raise NotImplementedError()
