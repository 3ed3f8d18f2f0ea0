"""Fully on-device self-play: env stepping + inference + sampling in ONE jit.

The thread-actor plane (runtime/worker.py + inference_engine.py) keeps the
reference's architecture — host envs, device model — and pays one host
round-trip per step wave. For envs that also exist as pure jnp transition
functions (envs/vector_tictactoe.py), this module removes the host from
the loop entirely: a ``lax.scan`` steps B games for max_steps, sampling
actions on device via Gumbel-max over legal-masked logits, and the ONLY
host work left is converting finished games into the standard columnar
episode format for the replay store. This is the actor-plane design point
the reference's process tree (worker.py:110-189) cannot express — per-step
throughput scales with the device batch, not with host round-trips.

Behavior parity with the host Generator (runtime/generation.py):
temperature-1 softmax sampling over legal-masked logits, recorded
behavior prob / action mask / critic value per turn player, discounted
returns (zero for reward-free games), identical columnar block schema —
pinned by tests/test_device_rollout.py, which replays every device game
through the host env.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.rows import COMMIT_SCOPE, acting_rows, put_rows
from ..utils import tree_map
from ..utils.compile_cache import scoped_program_options
from .replay import compress_block

ILLEGAL = 1e32

# XLA module names of this file's two programs: ``jax.jit`` names a module
# ``jit_<fn.__name__>``, and that name is what a device profile shows and
# what the benchmark's per-layer readers look up
STREAM_PROGRAM = "device_rollout"
EPISODE_PROGRAM = "device_rollout_episodes"
# ``jax.named_scope``s round the calls in the streaming program's scan body:
# components of its ops' ``op_name`` in a device profile (the benchmark's
# ``rollout_env_share`` imports them; docs/observability.md has the naming
# rule).  They sit in the body, not in the env, so device eval, which calls
# the same env, carries none of them
RESET_SCOPE = "env_reset"
OBSERVE_SCOPE = "env_observe"
POLICY_SCOPE = "rollout_policy"
ACT_SCOPE = "rollout_act"
STEP_SCOPE = "env_step"
ENV_SCOPES = (RESET_SCOPE, OBSERVE_SCOPE, STEP_SCOPE)
STREAM_SCOPES = (RESET_SCOPE, OBSERVE_SCOPE, POLICY_SCOPE, ACT_SCOPE, STEP_SCOPE)
# a recurrent module's per-(lane, player) hidden: zeroed where a lane starts
# again, committed where the player observed.  Whole-tree passes that are
# neither the env's work nor the net's, or, where one player a lane observes,
# the gather and the scatters of the acting rows (``ops/rows.py``, whose
# constant this is; not of a leaf's rows that the module steps where they
# lie: ``rows_in_place``); a module without hidden has no such op
# the flax collection a module's step mode may sow counters into (a routed
# layer: the rows its held experts computed, its row buffer's slots); the scan
# body adds ``ROWS_APPLIED``, the rows it applied the net to
COUNTERS = "counters"
ROWS_APPLIED = "rows_applied"


def build_selfplay_fn(venv, module, n_games: int):
    """Compile-once device self-play for a VectorTicTacToe-style env.

    Returns ``fn(params, rng_key) -> columns`` (jitted), where columns are
    time-major device arrays over the full max_steps horizon:
        obs    (T, B, ...)  turn player's observation
        prob   (T, B)       behavior probability of the selected action
        action (T, B) int32
        amask  (T, B, A)    0 legal / 1e32 illegal at selection time
        value  (T, B)       critic output at acting time
        alive  (T, B)       1.0 while the game was still running
        outcome (B, P)      final per-player scores
    """

    def fn(params, key):
        keys = jax.random.split(key, venv.max_steps)

        # strict alternation lets the step index be a Python int: unroll
        # over max_steps (9 for TicTacToe) so observation/turn math is
        # static per step while the games stay batched on device
        cols = {"obs": [], "prob": [], "action": [], "amask": [], "value": [], "alive": []}
        state = venv.init(n_games)
        for t in range(venv.max_steps):
            alive = ~venv.terminal(state, t)
            obs = venv.observation(state, t)
            out = module.apply({"params": params}, obs, None)
            logits = out["policy"].astype(jnp.float32)
            amask = jnp.where(venv.legal_mask(state), 0.0, ILLEGAL)
            masked = logits - amask
            # Gumbel-max == sampling from softmax(masked) (generation.py
            # samples softmax at temperature 1)
            g = jax.random.gumbel(keys[t], masked.shape)
            action = jnp.argmax(masked + g, axis=-1)
            probs = jax.nn.softmax(masked, axis=-1)
            prob = jnp.take_along_axis(probs, action[:, None], axis=-1)[:, 0]

            cols["obs"].append(obs)
            cols["prob"].append(prob)
            cols["action"].append(action.astype(jnp.int32))
            cols["amask"].append(amask)
            cols["value"].append(out["value"][:, 0] if out.get("value") is not None else jnp.zeros_like(prob))
            cols["alive"].append(alive.astype(jnp.float32))
            state = venv.apply(state, action, t)

        stacked = {k: jnp.stack(v) for k, v in cols.items()}
        stacked["outcome"] = venv.outcome(state)
        return stacked

    fn.__name__ = EPISODE_PROGRAM
    return jax.jit(fn)


def columns_to_episodes(host_cols: Dict[str, Any], venv, args: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Device rollout columns -> standard columnar episodes (the schema of
    Generator._finalize, runtime/generation.py) ready for EpisodeStore."""
    P = venv.num_players
    A = venv.num_actions
    alive = np.asarray(host_cols["alive"])               # (T, B)
    lengths = alive.sum(axis=0).astype(np.int32)         # (B,)
    outcome = np.asarray(host_cols["outcome"])           # (B, P)
    obs = np.asarray(host_cols["obs"])                   # (T, B, ...)
    prob = np.asarray(host_cols["prob"])
    action = np.asarray(host_cols["action"])
    amask = np.asarray(host_cols["amask"])
    value = np.asarray(host_cols["value"])

    block_len = args["compress_steps"]
    players = list(range(P))
    episodes = []
    for b in range(obs.shape[1]):
        T = int(lengths[b])
        if T == 0:
            continue
        blocks = []
        for lo in range(0, T, block_len):
            hi = min(lo + block_len, T)
            t = hi - lo
            ts = np.arange(lo, hi)
            tp = ts % P                                   # turn player per step
            cols = {
                "prob": np.ones((t, P), np.float32),
                "action": np.zeros((t, P), np.int32),
                "amask": np.full((t, P, A), ILLEGAL, np.float32),
                "value": np.zeros((t, P), np.float32),
                "reward": np.zeros((t, P), np.float32),
                "ret": np.zeros((t, P), np.float32),
                "tmask": np.zeros((t, P), np.float32),
                "omask": np.zeros((t, P), np.float32),
                "turn": tp.astype(np.int32),
            }
            rows = np.arange(t)
            cols["prob"][rows, tp] = prob[ts, b]
            cols["action"][rows, tp] = action[ts, b]
            cols["amask"][rows, tp] = amask[ts, b]
            cols["value"][rows, tp] = value[ts, b]
            cols["tmask"][rows, tp] = 1.0
            cols["omask"][rows, tp] = 1.0
            obs_block = np.zeros((t, P) + obs.shape[2:], np.float32)
            obs_block[rows, tp] = obs[ts, b]
            cols["obs"] = obs_block
            blocks.append(compress_block(cols))
        episodes.append(
            {
                "args": {"player": players, "model_id": {p: -1 for p in players}},
                "steps": T,
                "players": players,
                "outcome": {p: float(outcome[b, p]) for p in players},
                "blocks": blocks,
            }
        )
    return episodes


class DeviceRollout:
    """Compile-once wrapper: generate whole batches of finished episodes
    with a single device call each."""

    def __init__(self, venv, module, args: Dict[str, Any], n_games: int = 256):
        self.venv = venv
        self.args = args
        self.n_games = n_games
        self._fn = build_selfplay_fn(venv, module, n_games)

    def generate(self, params, key) -> List[Dict[str, Any]]:
        from ..parallel.mesh import dispatch_serialized

        # the episodic program is unsharded (it commits to the default
        # device), but the rollout thread dispatches it CONCURRENTLY with
        # sharded train steps whose device set includes that device — the
        # enqueue needs the same per-device program order as every other
        # dispatch site (the device scope is exactly the one device)
        cols = dispatch_serialized(
            lambda: self._fn(params, key), jax.devices()[:1]
        )
        # whole-horizon episodic fetch: this driver's contract IS one
        # host round-trip per batch of finished games
        # graftlint: allow[HS001] reason=episodic driver fetches one whole-horizon batch per call by design
        return columns_to_episodes(jax.device_get(cols), self.venv, self.args)


# ---------------------------------------------------------------------------
# Streaming rollout for simultaneous-move envs (VectorHungryGeese)
# ---------------------------------------------------------------------------


def build_streaming_fn(venv, module, n_lanes: int, k_steps: int, mesh=None,
                       use_observe_mask: bool = True, counters: bool = False):
    """Compile-once streaming self-play step for a simultaneous-move vector
    env (``venv.simultaneous``): ``fn(params, state, key) -> (state, record)``
    scans ``k_steps`` game steps over ``n_lanes`` persistent lanes,
    auto-resetting finished lanes at each iteration start so no device work
    is wasted on dead games.  Episodes are stitched across calls by
    StreamingDeviceRollout from the COMPACT per-step record (occupancy +
    heads + food, not full observation planes) — ~40x less HBM->host
    traffic than shipping the 17-plane observations, which the host
    reconstructs with pure numpy scatter ops.

    With ``mesh``, lanes shard over the mesh's 'dp' axis (params
    replicated): one SPMD program steps n_lanes games across all devices,
    the self-play analogue of the data-parallel train step.

    Works for simultaneous-move envs (every active player acts, e.g.
    VectorHungryGeese) and strict-alternation envs (``state['active']``
    one-hots the turn player, e.g. VectorGeister); recurrent modules
    (DRC ConvLSTM) carry per-(lane, player) hidden state across steps,
    zeroed on lane reset and committed where the player observed —
    matching the host generator's per-player hidden handling.

    Where exactly one player a lane observes (a recurrent module on a
    strict-alternation env without ``use_observe_mask``: ``observing`` is
    ``active``, one-hot once ``reset_done`` has run), a step is the acting
    rows' alone: the net is applied to ``n_lanes`` rows, not ``n_lanes x P``;
    of the hidden tree the acting player's row of each lane is gathered, read
    as zeros where the lane's game has just begun, stepped and scattered
    back, and the lane's other rows are written as zeros there and then; a
    leaf the module says it steps in place (``rows_in_place``: a ``HybridNet``'s
    mixers' states, off a mesh) is handed over whole with ``rows=(player,
    begun)`` and comes back whole.  The other rows of a step's ``action``, ``prob`` and
    ``value`` are what an episode holds for a player who does not act (0, 1,
    0); the Gumbel draw keeps its ``(n_lanes, P, A)`` shape, so the acting
    rows draw what they drew.  The same commit-where-observed, with a row
    count that is static here and not elsewhere.

    With ``counters`` the program has a fourth output: what the module's
    step mode sows into its ``counters`` collection, each name summed over
    the module's layers and the dispatch's steps, and ``rows_applied``, the
    rows the net was applied to over the dispatch's steps.  The records and
    the other outputs are what they are without it."""

    P = venv.num_players
    stateful = module.initial_state((1, 1)) is not None
    by_row = (stateful and not getattr(venv, "simultaneous", True)
              and not (use_observe_mask and hasattr(venv, "observe_mask")))
    # a kernel's operand is not GSPMD's to shard: on a mesh every leaf is gathered
    claims = getattr(module, "rows_in_place", None) if by_row and mesh is None else None

    def fn(params, state, hidden, key):
        def body(carry, key_t):
            state, hidden = carry
            kr, ka, kf = jax.random.split(key_t, 3)
            reset = state["done"]
            with jax.named_scope(RESET_SCOPE):
                state = venv.reset_done(state, kr)
            if hidden is not None and not by_row:
                with jax.named_scope(COMMIT_SCOPE):
                    # fresh games start from zero hidden (host: init_hidden)
                    hidden = tree_map(
                        lambda h: h * ~reset.reshape((-1,) + (1,) * (h.ndim - 1)),
                        hidden,
                    )
            active = state["active"]                     # (B, P) acting mask
            B = active.shape[0]
            with jax.named_scope(OBSERVE_SCOPE):
                # observe_mask (observer views for non-acting players) applies
                # only under ``observation: true`` — with it false the host
                # generator records turn players only, and the device path must
                # emit the same omask semantics into the shared replay store
                observing = (
                    venv.observe_mask(state)
                    if use_observe_mask and hasattr(venv, "observe_mask")
                    else active
                )
                obs = venv.observation(state)            # leaves (B, P, ...)
                if by_row:
                    lanes, player = jnp.arange(B), jnp.argmax(active, axis=1).astype(jnp.int32)
                    acting = lambda x: x[lanes, player]  # noqa: E731  (B, P, ...) -> (B, ...)
                    flat = tree_map(acting, obs)
                else:
                    flat = tree_map(lambda x: x.reshape((B * P,) + x.shape[2:]), obs)
            with jax.named_scope(POLICY_SCOPE):
                how = {}
                if by_row:
                    if claims is None:
                        whole = tree_map(lambda h: False, hidden)
                    else:
                        whole, how = claims(hidden), {"rows": (player, reset)}
                    with jax.named_scope(COMMIT_SCOPE):
                        # fresh games start from zero hidden (host: init_hidden)
                        h_flat = tree_map(
                            lambda h, kept: h if kept else acting_rows(h, player, reset),
                            hidden, whole)
                else:
                    h_flat = (
                        None
                        if hidden is None
                        else tree_map(lambda h: h.reshape((B * P,) + h.shape[2:]), hidden)
                    )
                counted = {}
                if counters:
                    out, sown = module.apply(
                        {"params": params}, flat, h_flat, mutable=[COUNTERS], **how)
                    for path, value in jax.tree_util.tree_leaves_with_path(
                            sown.get(COUNTERS, {})):
                        # .../<name>/<index of the call that sowed it>
                        name = path[-2].key
                        counted[name] = counted.get(name, 0.0) + value.astype(jnp.float32)
                    counted[ROWS_APPLIED] = jnp.float32(B if by_row else B * P)
                else:
                    out = module.apply({"params": params}, flat, h_flat, **how)
                if by_row:
                    with jax.named_scope(COMMIT_SCOPE):
                        # the acting row back where it lay; the lane's other
                        # rows zeroed where its game has just begun
                        hidden = tree_map(
                            lambda h, nh, kept: nh if kept else put_rows(h, nh, player, reset),
                            hidden, out["hidden"], whole)
                elif hidden is not None:
                    new_hidden = tree_map(
                        lambda h: h.reshape((B, P) + h.shape[1:]), out["hidden"]
                    )
                    with jax.named_scope(COMMIT_SCOPE):
                        # commit where observed, keep elsewhere (train_step.py:146)
                        hidden = jax.tree.map(
                            lambda h, nh: jnp.where(
                                observing.reshape((B, P) + (1,) * (h.ndim - 2)), nh, h
                            ),
                            hidden,
                            new_hidden,
                        )
            with jax.named_scope(ACT_SCOPE):
                if by_row:
                    logits = out["policy"].astype(jnp.float32)      # (B, A): the acting rows'
                    legal = venv.legal_mask_all(state)
                    masked = jnp.where(acting(legal), logits, logits - ILLEGAL)
                    g = acting(jax.random.gumbel(ka, legal.shape))
                else:
                    logits = out["policy"].astype(jnp.float32).reshape(B, P, -1)
                    legal = venv.legal_mask_all(state)       # (B, P, A) bool
                    masked = jnp.where(legal, logits, logits - ILLEGAL)
                    # Gumbel-max == softmax sampling at temperature 1 (generation.py)
                    g = jax.random.gumbel(ka, masked.shape)
                action = jnp.argmax(masked + g, axis=-1).astype(jnp.int32)
                probs = jax.nn.softmax(masked, axis=-1)
                prob = jnp.take_along_axis(probs, action[..., None], axis=-1)[..., 0]
                value = (
                    # float32 like prob, whatever the net computes in: the
                    # records' schema is the rings', not the net's
                    out["value"].astype(jnp.float32).reshape(prob.shape)
                    if out.get("value") is not None
                    else jnp.zeros_like(prob)
                )
                if by_row:  # (B,) -> (B, P): what an episode holds for the other rows
                    action, prob, value = (
                        jnp.where(active, x[:, None], idle)
                        for x, idle in ((action, 0), (prob, 1.0), (value, 0.0)))
            with jax.named_scope(STEP_SCOPE):
                record = {
                    "active": active,
                    "observing": observing,
                    "legal": legal,
                    "action": action.astype(jnp.int32),
                    "prob": prob,
                    "value": value,
                }
                record.update(venv.record(state))   # env's compact obs fields
                state = venv.step(state, action, kf)
                record["done"] = state["done"]   # reset_done cleared stale flags
                record["outcome"] = venv.outcome_scores(state)  # final where done
            return (state, hidden), (record, counted)

        # Stays a genuine loop on every backend: unrolling k_steps bodies
        # here multiplies compile time by k (measured: minutes per shape on
        # the 1-core CPU host) for a path whose CPU throughput is a
        # fallback, not a target — unlike the RNN TRAIN scan, which is
        # unrolled on single-device CPU (see parallel/train_step.py).
        (state, hidden), (records, counted) = jax.lax.scan(
            body, (state, hidden), jax.random.split(key, k_steps)
        )
        if counters:
            return state, hidden, records, {k: v.sum() for k, v in counted.items()}
        return state, hidden, records

    fn.__name__ = STREAM_PROGRAM
    # a module without hidden keeps the options, and so the cache entry, it had
    options = scoped_program_options(*STREAM_SCOPES, *((COMMIT_SCOPE,) if stateful else ()))
    if mesh is None:
        return jax.jit(fn, donate_argnums=(1, 2), compiler_options=options)
    from jax.sharding import NamedSharding, PartitionSpec

    lanes = NamedSharding(mesh, PartitionSpec("dp"))            # state: (B, ...)
    rec = NamedSharding(mesh, PartitionSpec(None, "dp"))        # record: (K, B, ...)
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(
        fn,
        donate_argnums=(1, 2),
        in_shardings=(rep, lanes, lanes, rep),
        out_shardings=(lanes, lanes, rec) + ((rep,) if counters else ()),
        compiler_options=options,
    )


def _streaming_episode(venv, steps: List[tuple], done_rec, done_k: int, lane: int,
                       args: Dict[str, Any]) -> Dict[str, Any]:
    """Assemble one finished lane into the standard columnar episode.

    ``steps`` is the lane's buffered [(record, k_start, k_end)] span
    history (possibly spanning several device calls); observations are
    rebuilt host-side from the env's compact record fields
    (``venv.episode_obs``) — pinned against the host env's observation()
    by tests/test_device_rollout.py."""
    P = venv.num_players
    T = sum(k1 - k0 for _, k0, k1 in steps)
    b = lane

    def gather(name, dtype=None):
        out = np.concatenate(
            [np.asarray(rec[name][k0:k1, b]) for rec, k0, k1 in steps]
        )
        return out if dtype is None else out.astype(dtype)

    action = gather("action", np.int32)    # (T, P)
    prob = gather("prob", np.float32)
    value = gather("value", np.float32)
    active = gather("active", np.float32)  # (T, P) 0/1 — acted this step
    observing = gather("observing", np.float32)      # (T, P) 0/1
    legal = gather("legal")                # (T, P, A) bool
    compact = {
        name: gather(name)
        for name in steps[0][0]
        if name not in ("active", "observing", "legal", "action",
                        "prob", "value", "done", "outcome")
    }
    obs = venv.episode_obs(compact, observing)       # (T, P, ...)

    final = np.asarray(done_rec["outcome"][done_k][b], np.float32)
    players = list(range(P))
    outcome = {p: float(final[p]) for p in players}

    # per-step reward (constant-per-step envs, e.g. Geister's -0.01) and
    # its discounted return-to-go (generation.py:78-82, 101-103 — rewards
    # accrue to every player each step)
    step_reward = float(getattr(venv, "step_reward", 0.0))
    reward = np.full((T, P), step_reward, np.float32)
    ret = np.zeros((T, P), np.float32)
    if step_reward:
        acc = np.zeros(P, np.float32)
        for t in range(T - 1, -1, -1):
            acc = reward[t] + args["gamma"] * acc
            ret[t] = acc

    block_len = args["compress_steps"]
    blocks = []
    for lo in range(0, T, block_len):
        hi = min(lo + block_len, T)
        act = active[lo:hi]
        obsv = observing[lo:hi]
        amask = np.where(
            legal[lo:hi] & (act[..., None] > 0), 0.0, ILLEGAL
        ).astype(np.float32)
        cols = {
            "obs": tree_map(lambda x: x[lo:hi], obs),
            "prob": np.where(act > 0, prob[lo:hi], 1.0).astype(np.float32),
            "action": (action[lo:hi] * (act > 0)).astype(np.int32),
            "amask": amask,
            "value": (value[lo:hi] * obsv).astype(np.float32),
            "reward": reward[lo:hi],
            "ret": ret[lo:hi],
            "tmask": act.astype(np.float32),
            "omask": obsv.astype(np.float32),
            "turn": np.argmax(act, axis=1).astype(np.int32),
        }
        blocks.append(compress_block(cols))

    return {
        "args": {"player": players, "model_id": {p: -1 for p in players}},
        "steps": T,
        "players": players,
        "outcome": outcome,
        "blocks": blocks,
    }


def make_device_rollout(venv, module, args: Dict[str, Any], n_games: int, mesh=None):
    """Pick the rollout driver for a vector env: persistent streaming
    lanes for envs exposing the streaming hooks (VectorHungryGeese,
    VectorParallelTicTacToe, VectorGeister) — lanes sharded over the
    mesh's 'dp' axis when a mesh is given — else episodic whole-horizon
    calls (VectorTicTacToe's 9-ply games)."""
    if hasattr(venv, "record"):
        return StreamingDeviceRollout(venv, module, args, n_lanes=n_games, mesh=mesh)
    if module.initial_state((1,)) is not None:
        # build_selfplay_fn steps with hidden=None (fresh state every ply):
        # a stateful policy self-plays MEMORYLESSLY on this driver.  The
        # recorded behavior probs are still the true behavior policy, so
        # training stays sound (off-policy corrections), but the data is
        # not what host actors (which carry hidden) would generate — say so
        import sys

        print(
            "[handyrl_tpu] episodic device rollout steps a stateful model "
            "(RNN/KV-cache) with a fresh hidden state every ply — self-play "
            "is memoryless on this driver; for memory-faithful device "
            "self-play give the env a streaming vector twin (record/"
            "reset_done/step hooks), or use host actors",
            file=sys.stderr,
        )
    return DeviceRollout(venv, module, args, n_games)


class StreamingDeviceRollout:
    """Persistent-lane self-play for simultaneous-move vector envs.

    Each ``generate`` call advances every lane ``k_steps`` game steps in
    ONE device call and returns the episodes that finished; in-progress
    games carry over (their lanes keep stepping next call).  Lanes reset
    the moment their game ends, so device utilization is independent of
    episode length — the design point behind the HungryGeese north star.

    Params may change between calls (the learner publishes new epochs);
    in-flight games finish under the newest params and are credited to the
    model_id the caller stamps at flush time — the same staleness the
    IMPALA off-policy corrections (ops/losses.py) already absorb.
    """

    def __init__(self, venv, module, args: Dict[str, Any], n_lanes: int = 256,
                 k_steps: int = 32, mesh=None):
        if mesh is not None:
            dp = mesh.shape.get("dp", 1)
            if n_lanes % dp:
                raise ValueError(f"n_lanes {n_lanes} not divisible by dp axis {dp}")
        self.venv = venv
        self.args = args
        self.n_lanes = n_lanes
        self.k_steps = k_steps
        self.module = module
        # mesh (or None): the device set the dispatch locks cover — a
        # split-plane actor mesh dispatches concurrently with the learner
        # plane; mesh-less rollouts keep the conservative all-device locks
        self.mesh = mesh
        self._fn = build_streaming_fn(
            venv, module, n_lanes, k_steps, mesh,
            use_observe_mask=bool(args.get("observation", False)),
        )
        self._state = None
        self._hidden = None
        self._pending = None         # in-flight device record (one-call pipeline)
        self._partial: List[List[tuple]] = [[] for _ in range(n_lanes)]
        self.game_steps = 0          # lifetime game-steps (>=1 player acting)
        self.player_steps = 0        # lifetime per-player acting steps

    def generate(self, params, key) -> List[Dict[str, Any]]:
        """Advance all lanes k_steps and return episodes finished one call
        ago: the device computes block N while the host transfers and
        assembles block N-1 (jax dispatch is async; only the device_get
        synchronizes), so host-side episode assembly is hidden behind
        device compute instead of serializing with it."""
        import jax as _jax

        if self._state is None:
            key, k0 = _jax.random.split(key)
            self._state = self.venv.init(self.n_lanes, k0)
            self._hidden = self.module.initial_state(
                (self.n_lanes, self.venv.num_players)
            )
        from ..parallel.mesh import dispatch_serialized

        # consistent cross-device program order vs concurrent programs on
        # an overlapping device set (and serialization with them on the
        # CPU backend) — the dispatch is async on TPU, so execution still
        # overlaps the assembly below; on a split-plane actor mesh the
        # locks cover only the actor devices, so the learner plane's train
        # dispatches proceed concurrently
        self._state, self._hidden, record = dispatch_serialized(
            lambda: self._fn(params, self._state, self._hidden, key),
            self.mesh,
        )
        record, self._pending = self._pending, record
        if record is None:
            return []
        # graftlint: allow[HS001] reason=one-call-pipelined fetch: block N-1's transfer overlaps block N's device compute (the dispatch above is async)
        record = _jax.device_get(record)

        active = record["active"]                    # (K, B, P)
        self.game_steps += int((active.sum(axis=2) > 0).sum())
        self.player_steps += int(active.sum())

        # span bookkeeping: one (record, k0, k1) entry per lane per call in
        # the common case — not one append per lane per STEP, which at
        # 512 lanes x 32 steps costs ~16k interpreter appends on the very
        # host thread the compute/assembly overlap is keeping light
        episodes = []
        done = record["done"]                        # (K, B)
        lane_has_done = done.any(axis=0)
        K = self.k_steps
        for b in range(self.n_lanes):
            if not lane_has_done[b]:
                self._partial[b].append((record, 0, K))
                continue
            seg = 0
            for kd in np.flatnonzero(done[:, b]):
                kd = int(kd)
                self._partial[b].append((record, seg, kd + 1))
                episodes.append(
                    _streaming_episode(
                        self.venv, self._partial[b], record, kd, b, self.args
                    )
                )
                self._partial[b] = []
                seg = kd + 1        # the lane resets at kd + 1 (next episode)
            if seg < K:
                self._partial[b].append((record, seg, K))
        return episodes

    def drain(self) -> None:
        """Block on the in-flight device block.  MUST be called before the
        owning process exits: tearing down the runtime while an async
        dispatch is still executing cancels XLA's worker threads mid-thunk
        and aborts the process (observed as 'FATAL: exception not
        rethrown' at interpreter exit)."""
        import jax as _jax

        if self._pending is not None:
            _jax.block_until_ready(self._pending)
