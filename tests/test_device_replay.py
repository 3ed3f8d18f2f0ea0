"""Device-resident replay (runtime/device_replay.py) parity tests.

The bar: a window sampled and assembled ON DEVICE must equal, key by key,
the batch the host path (StreamingDeviceRollout episode assembly ->
EpisodeStore window -> make_batch) builds for the SAME episode, window
start, and target player.  Both paths consume the identical streaming-fn
records, so every difference is an assembly bug, not sampling noise.
"""

import jax
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.envs.vector_hungry_geese import VectorHungryGeese
from handyrl_tpu.models import init_variables
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.runtime.batch import make_batch
from handyrl_tpu.runtime.device_replay import DeviceReplay
from handyrl_tpu.runtime.device_rollout import _streaming_episode, build_streaming_fn
from handyrl_tpu.utils import tree_map

N_LANES = 8
K_STEPS = 32
N_CALLS = 10          # 320 steps > SLOTS: the ring wraps and invalidation runs
SLOTS = 192


def _args(env_name: str = "HungryGeese", **overrides):
    train = {
        "turn_based_training": False,
        "observation": False,
        "batch_size": 8,
        "forward_steps": 8,
        "burn_in_steps": 0,
    }
    train.update(overrides)
    cfg = normalize_args(
        {"env_args": {"env": env_name}, "train_args": train}
    )
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    return args


def _drive_rollout(env_name: str, venv, n_lanes: int, k_steps: int,
                   n_calls: int, slots: int, **arg_overrides):
    """Drive the streaming fn once; return the host episodes (with their
    [lane, g0, g1] global-step spans) and a DeviceReplay holding the SAME
    records — the two sides every parity check compares."""
    env = make_env({"env": env_name})
    module = env.net()
    params = init_variables(module, env)["params"]
    args = _args(env_name, **arg_overrides)

    mesh = make_mesh({"dp": 1})
    fn = build_streaming_fn(venv, module, n_lanes, k_steps, mesh=None,
                            use_observe_mask=bool(args["observation"]))
    replay = DeviceReplay(venv, module, args, mesh, n_lanes, slots=slots)

    state = venv.init(n_lanes, jax.random.PRNGKey(7))
    hidden = module.initial_state((n_lanes, venv.num_players))
    key = jax.random.PRNGKey(42)
    chunks = []
    for _ in range(n_calls):
        key, sub = jax.random.split(key)
        state, hidden, records = fn(params, state, hidden, sub)
        records = jax.device_get(records)
        chunks.append(records)
        replay.ingest(tree_map(np.asarray, records))

    full = tree_map(lambda *xs: np.concatenate(xs), *chunks)  # (G, B, ...)
    G = n_calls * k_steps

    episodes = []                     # (lane, g0, g1, host episode dict)
    done = full["done"]               # (G, B)
    for b in range(n_lanes):
        g0 = 0
        for g1 in np.flatnonzero(done[:, b]):
            g1 = int(g1)
            ep = _streaming_episode(venv, [(full, g0, g1 + 1)], full, g1, b, args)
            episodes.append((b, g0, g1, ep))
            g0 = g1 + 1
    assert len(episodes) >= 10, "rollout produced too few finished episodes"
    return {
        "episodes": episodes, "replay": replay, "module": module,
        "params": params, "args": args, "G": G, "mesh": mesh,
        "n_lanes": n_lanes, "slots": slots,
    }


@pytest.fixture(scope="module")
def rollout_data():
    return _drive_rollout("HungryGeese", VectorHungryGeese,
                          N_LANES, K_STEPS, N_CALLS, SLOTS)


def _host_window(ep, train_start, args):
    """Reconstruct the exact sample_window dict (replay.py:110-140) for a
    forced train_start."""
    fwd, cs = args["forward_steps"], args["compress_steps"]
    steps = ep["steps"]
    start = max(0, train_start - args["burn_in_steps"])
    end = min(train_start + fwd, steps)
    first_block = start // cs
    last_block = (end - 1) // cs + 1
    return {
        "args": ep["args"],
        "outcome": np.asarray([ep["outcome"][p] for p in ep["players"]], np.float32),
        "players": ep["players"],
        "blocks": ep["blocks"][first_block:last_block],
        "base": first_block * cs,
        "start": start,
        "end": end,
        "train_start": train_start,
        "total": steps,
    }


def _check_windows(data, monkeypatch, n: int, seed: int = 3):
    """Key-by-key equality of device-assembled windows vs make_batch on the
    same (episode, train_start, target player)."""
    replay, args = data["replay"], data["args"]
    episodes = data["episodes"]
    G, S = data["G"], data["slots"]

    batch, info = replay.sample(jax.random.PRNGKey(seed), n, with_info=True)
    batch = tree_map(np.asarray, batch)

    for i in range(n):
        lane, slot, player = int(info["lane"][i]), int(info["slot"][i]), int(info["player"][i])
        gs0 = G - 1 - ((G - 1 - slot) % S)    # global step held by the slot
        hits = [e for e in episodes if e[0] == lane and e[1] <= gs0 <= e[2]]
        assert hits, f"sampled slot maps to no finished episode (lane {lane}, g {gs0})"
        b, g0, g1, ep = hits[0]
        # the device only samples eligible starts: finished episode, within
        # the host sampler's train_start range
        train_start = gs0 - g0
        assert train_start <= max(0, ep["steps"] - args["forward_steps"])

        if player >= 0:  # ff mode samples one target player per window
            monkeypatch.setattr(
                "handyrl_tpu.runtime.batch.random.randrange", lambda _n: player
            )
        host = make_batch([_host_window(ep, train_start, args)], args)

        for key in host:
            if key == "observation":  # pytree for some envs (Geister)
                for hl, dl in zip(jax.tree.leaves(host[key]), jax.tree.leaves(batch[key])):
                    np.testing.assert_allclose(
                        dl[i : i + 1], hl, atol=1e-6, err_msg=f"{key} row {i}"
                    )
            else:
                np.testing.assert_allclose(
                    batch[key][i : i + 1], host[key], atol=1e-6, err_msg=f"{key} row {i}"
                )


def test_sampled_windows_match_make_batch(rollout_data, monkeypatch):
    _check_windows(rollout_data, monkeypatch, n=48)


def test_parallel_tictactoe_device_replay_parity(monkeypatch):
    """The second device-replay env: VectorParallelTicTacToe windows must
    match make_batch the same way (9-step episodes, heavy auto-reset —
    many episodes per ring cycle, the opposite regime from geese)."""
    from handyrl_tpu.envs.vector_parallel_tictactoe import VectorParallelTicTacToe

    data = _drive_rollout("ParallelTicTacToe", VectorParallelTicTacToe,
                          n_lanes=4, k_steps=12, n_calls=6, slots=32)
    _check_windows(data, monkeypatch, n=32)


@pytest.fixture(scope="module")
def geister_rollout_data():
    """The turn-based + recurrent mode: VectorGeister with the DRC net,
    observation: true (both players' views + observer omask), burn-in 4."""
    from handyrl_tpu.envs.vector_geister import VectorGeister

    # random Geister games mostly reach the 200-ply draw, so each lane
    # needs ~700 steps to finish >=3 episodes
    return _drive_rollout(
        "Geister", VectorGeister, n_lanes=4, k_steps=32, n_calls=22,
        slots=256, turn_based_training=True, observation=True,
        burn_in_steps=4,
    )


@pytest.mark.slow  # ~3 min of jitted DRC rollout on the CPU mesh
def test_geister_turn_windows_match_make_batch(geister_rollout_data, monkeypatch):
    """Turn-mode device windows (all players, burn-in rows, DRC records)
    must equal make_batch key by key on the same episode + train_start."""
    _check_windows(geister_rollout_data, monkeypatch, n=32)


@pytest.mark.slow
def test_geister_turn_train_fn_runs(geister_rollout_data):
    """Recurrent sample+SGD straight from the rings: the train step's RNN
    scan consumes the device-assembled (B, T, P, ...) window (burn-in under
    stop_gradient) — finite loss, params move."""
    data = geister_rollout_data
    ctx = TrainContext(data["module"], data["args"], data["mesh"])
    state = ctx.init_state(data["params"])
    before = jax.device_get(state["params"])
    fn = data["replay"].train_fn(ctx, fused_steps=1)
    state, metrics = fn(state, jax.random.PRNGKey(11), 1e-3)
    m = jax.device_get(metrics)
    assert np.isfinite(m["total"]) and m["dcnt"] > 0
    after = jax.device_get(state["params"])
    assert max(
        float(np.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
    ) > 0, "params did not move"


def test_transformer_turn_mode_trains_from_rings():
    """The transformer family (KV-cache hidden, seq-attention training)
    through turn-mode device replay: streamed Geister records ingest into
    rings, windows assemble on device, and the seq-path train step
    consumes them — finite loss, real data count.  Completes the
    model-family x data-path matrix (DRC was the only turn-mode net)."""
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.envs.vector_geister import VectorGeister
    from handyrl_tpu.models import init_variables
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn

    env = make_env({
        "env": "Geister", "net": "transformer",
        "net_args": {"d_model": 32, "n_heads": 2, "n_layers": 2,
                     "memory_len": 8},
    })
    module = env.net()
    params = init_variables(module, env)["params"]
    cfg = normalize_args({
        "env_args": {"env": "Geister"},
        "train_args": {"turn_based_training": True, "observation": True,
                       "batch_size": 4, "forward_steps": 4,
                       "burn_in_steps": 2, "seq_attention": "einsum",
                       "mesh": {"dp": 1}},
    })
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    mesh = make_mesh({"dp": 1})
    lanes = 4
    fn = build_streaming_fn(VectorGeister, module, lanes, 64, mesh=None,
                            use_observe_mask=True)
    replay = DeviceReplay(VectorGeister, module, args, mesh, lanes, slots=64)
    state = VectorGeister.init(lanes, jax.random.PRNGKey(3))
    hidden = module.initial_state((lanes, VectorGeister.num_players))
    key = jax.random.PRNGKey(4)
    for _ in range(5):
        key, sub = jax.random.split(key)
        state, hidden, records = fn(params, state, hidden, sub)
        replay.ingest(records)
    assert replay.eligible_count() > 0
    ctx = TrainContext(module, args, mesh)
    train = replay.train_fn(ctx, fused_steps=1)
    tstate, metrics = train(ctx.init_state(params), jax.random.PRNGKey(5), 1e-4)
    m = jax.device_get(metrics)
    assert np.isfinite(m["total"]) and m["dcnt"] > 0


def test_eligibility_and_wrap(rollout_data):
    """After the ring wraps, every eligible slot belongs to a finished,
    still-resident episode — and partially-overwritten episodes only offer
    window starts whose full window is resident."""
    from handyrl_tpu.runtime.device_replay import _eligibility

    replay = rollout_data["replay"]
    episodes = rollout_data["episodes"]
    args = rollout_data["args"]
    G, S = rollout_data["G"], SLOTS
    assert G > S, "test must exercise ring wrap"

    ok = np.asarray(_eligibility(replay.rings, args["forward_steps"]))
    assert ok.any(), "no eligible slots after ingest"
    spans = {}
    for b, g0, g1, ep in episodes:
        spans.setdefault(b, []).append((g0, g1))
    for b in range(N_LANES):
        for s in np.flatnonzero(ok[b]):
            gs = G - 1 - ((G - 1 - int(s)) % S)
            in_ep = [sp for sp in spans.get(b, []) if sp[0] <= gs <= sp[1]]
            assert in_ep, f"eligible slot outside any finished episode (lane {b})"
            g0, g1 = in_ep[0]
            # episode end must still be resident (windows read forward)
            assert g1 > G - 1 - S


def _ring_mask(fixture):
    def build(request):
        data = request.getfixturevalue(fixture)
        from handyrl_tpu.runtime.device_replay import _eligibility

        args = data["args"]
        return np.asarray(_eligibility(
            data["replay"].rings, args["forward_steps"], args["burn_in_steps"]
        ))

    return build


def _hand_mask(*eligible):
    def build(request):
        ok = np.zeros((5, 7), bool)
        for lane, slot in eligible:
            ok[lane, slot] = True
        return ok

    return build


@pytest.mark.parametrize("build", [
    pytest.param(_ring_mask("rollout_data"), id="ff_wrapped_ring"),
    pytest.param(_ring_mask("geister_rollout_data"), id="turn_burn_in"),
    pytest.param(_hand_mask((0, 0)), id="only_first_slot"),
    pytest.param(_hand_mask((-1, -1)), id="only_last_slot"),
    pytest.param(_hand_mask((3, 0)), id="first_slot_after_empty_lanes"),
    pytest.param(_hand_mask(), id="nothing_eligible"),
])
def test_draw_starts_uniform_over_the_mask(build, request):
    """The inverse-CDF draw: every drawn (lane, slot) is eligible, every
    eligible slot is drawn, and the hit counts pass a chi-square test
    against uniform (fixed key, so deterministic: the statistic must stay
    under its mean + 5 standard deviations, dof + 5 * sqrt(2 * dof)).  A
    mask with one eligible slot pins the search's side and the empty-lane
    case (statistic 0 with 0 degrees of freedom); an empty mask gives the
    in-range (0, 0)."""
    import jax.numpy as jnp

    from handyrl_tpu.runtime.device_replay import _draw_starts

    ok = build(request)
    n = 60_000 if ok.sum() > 1 else 256
    lane, slot = jax.jit(_draw_starts, static_argnums=2)(
        jnp.asarray(ok), jax.random.PRNGKey(0), n
    )
    lane, slot = np.asarray(lane), np.asarray(slot)
    assert lane.shape == slot.shape == (n,)
    assert lane.dtype == slot.dtype == np.int32
    if not ok.any():
        assert not lane.any() and not slot.any()
        return
    assert ((0 <= lane) & (lane < ok.shape[0])).all()
    assert ((0 <= slot) & (slot < ok.shape[1])).all()
    assert ok[lane, slot].all(), "drew an ineligible window start"
    hits = np.zeros(ok.shape, np.int64)
    np.add.at(hits, (lane, slot), 1)
    assert (hits[ok] > 0).all(), "an eligible window start was never drawn"
    dof = int(ok.sum()) - 1
    expected = n / ok.sum()
    chi2 = float(((hits[ok] - expected) ** 2 / expected).sum())
    assert chi2 <= dof + 5 * np.sqrt(2 * dof), (chi2, dof)


def test_draw_independent_of_ring_sharding(rollout_data):
    """Lane-sharded rings (dp=4 on the forced 8-device CPU mesh) draw the
    same windows as the same rings on one device, and assemble the same
    batch from them."""
    from handyrl_tpu.runtime.device_replay import _lane_sharding

    one = rollout_data["replay"]
    mesh = make_mesh({"dp": 4})
    sharded = DeviceReplay(VectorHungryGeese, rollout_data["module"],
                           rollout_data["args"], mesh, N_LANES, slots=SLOTS)
    sharded.rings = jax.device_put(one.rings, _lane_sharding(mesh, one.rings))
    sharded.row_format = one.row_format
    assert len(sharded.rings["valid"].sharding.device_set) == 4

    key = jax.random.PRNGKey(9)
    batch_1, info_1 = one.sample(key, 64, with_info=True)
    batch_4, info_4 = sharded.sample(key, 64, with_info=True)
    for k in ("lane", "slot", "player"):
        np.testing.assert_array_equal(info_4[k], info_1[k], err_msg=k)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
        batch_4, batch_1,
    )


def test_train_fn_runs_and_updates(rollout_data):
    """Fused sample+SGD from the rings: finite loss, params actually move,
    metrics summed over fused steps (dcnt ~ fused * batch turn sum)."""
    replay = rollout_data["replay"]
    module, params, args = (
        rollout_data["module"], rollout_data["params"], rollout_data["args"],
    )
    ctx = TrainContext(module, args, rollout_data["mesh"])
    state = ctx.init_state(params)
    before = jax.device_get(state["params"])
    fn = replay.train_fn(ctx, fused_steps=2)
    state, metrics = fn(state, jax.random.PRNGKey(5), 1e-3)
    m = jax.device_get(metrics)
    assert np.isfinite(m["total"]) and m["dcnt"] > 0
    after = jax.device_get(state["params"])
    diffs = [
        float(np.abs(a - b).max())
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
    ]
    assert max(diffs) > 0, "params did not move"
    assert int(jax.device_get(state["steps"])) == 2


def test_learner_device_replay_end_to_end(tmp_path, monkeypatch):
    """Full --train stack with device_replay: the data path never builds a
    host episode, yet epochs advance, generation stats are booked from
    ingest counters, checkpoints land, and metrics.jsonl records updates."""
    import json
    import os

    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    cfg = normalize_args({
        "env_args": {"env": "HungryGeese"},
        "train_args": {
            "turn_based_training": False,
            "observation": False,
            "batch_size": 8,
            "forward_steps": 8,
            "minimum_episodes": 10,
            # the epoch cadence is episode-counted (reference semantics):
            # size the budget so the run outlasts the one-off CPU compile
            # of the fused sample+train step, else it ends with 0 updates
            "update_episodes": 40,
            "maximum_episodes": 1000,
            "epochs": 2,
            "eval_rate": 0.0,
            "device_rollout_games": 8,
            "device_replay": True,
            "device_replay_slots": 256,
            "device_replay_k_steps": 16,
            "worker": {"num_parallel": 1},
        },
    })
    learner = Learner(cfg)
    learner.run()

    records = [json.loads(l) for l in open("metrics.jsonl")]
    # `epochs` counts MODEL UPDATES; a metrics record is written at every
    # epoch boundary, including pre-warmup ones where the trainer had
    # nothing yet — on a loaded host that adds an extra leading record
    # (reproduced 2026-08-01 under a concurrent suite run)
    assert 2 <= len(records) <= 3
    assert records[-1]["steps"] > 0, "no SGD updates ran"
    assert records[-1]["episodes"] >= 80, "episode counters did not reach epoch 2"
    # generation stats came from device counters (host saw no episodes)
    assert any("generation_mean" in r for r in records)
    # per-epoch self-play volume -> survival signal in the metrics
    assert any(r.get("device_mean_episode_len", 0) > 1 for r in records)
    assert os.path.exists("models/latest.ckpt")
    assert os.path.exists("models/state.ckpt")
    assert learner.trainer.store.total_added == 0, (
        "device_replay must not materialize host episodes"
    )


@pytest.mark.slow
def test_learner_geister_device_replay_end_to_end(tmp_path, monkeypatch):
    """Full --train stack on the turn-based + recurrent mode: Geister DRC
    trained from device rings (burn-in windows, all-player batches), no
    host episodes materialized, epochs advance, checkpoints land."""
    import json
    import os

    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    cfg = normalize_args({
        "env_args": {"env": "Geister"},
        "train_args": {
            "turn_based_training": True,
            "observation": True,
            "batch_size": 4,
            "forward_steps": 4,
            "burn_in_steps": 2,
            "minimum_episodes": 2,
            "update_episodes": 2,
            "maximum_episodes": 100,
            "epochs": 1,
            "eval_rate": 0.0,
            "device_rollout_games": 2,
            "device_replay": True,
            "device_replay_slots": 256,
            "device_replay_k_steps": 64,
            "mesh": {"dp": 1},
            "worker": {"num_parallel": 1},
        },
    })
    learner = Learner(cfg)
    learner.run()

    records = [json.loads(l) for l in open("metrics.jsonl")]
    # epochs count model updates; pre-warmup boundaries may add a leading
    # record on a loaded host (see the geese test above)
    assert 1 <= len(records) <= 2
    assert records[-1]["steps"] > 0, "no SGD updates ran"
    assert os.path.exists("models/latest.ckpt")
    assert learner.trainer.store.total_added == 0, (
        "device_replay must not materialize host episodes"
    )


def test_ingest_counted_deferred_matches_sync(rollout_data):
    """The direct-ingest hot path (learner rollout thread): deferred stats
    fetching (ingest_counted defer=True + flush_counted) must land the
    same cumulative counters as the synchronous per-dispatch fetch — the
    deferral only moves WHEN the scalar fetch happens, never what it
    counts."""
    env = make_env({"env": "HungryGeese"})
    module = env.net()
    params = init_variables(module, env)["params"]
    args = rollout_data["args"]
    mesh = rollout_data["mesh"]
    fn = build_streaming_fn(VectorHungryGeese, module, 4, 16, mesh=None,
                            use_observe_mask=False)
    sync = DeviceReplay(VectorHungryGeese, module, args, mesh, 4, slots=64)
    deferred = DeviceReplay(VectorHungryGeese, module, args, mesh, 4, slots=64)
    state = VectorHungryGeese.init(4, jax.random.PRNGKey(21))
    key = jax.random.PRNGKey(22)
    chunks = []
    for _ in range(5):
        key, sub = jax.random.split(key)
        state, _, records = fn(params, state, None, sub)
        chunks.append(tree_map(np.asarray, jax.device_get(records)))
    returned_eps = 0
    for rec in chunks:
        sync.ingest_counted(rec)
        out = deferred.ingest_counted(rec, defer=True)
        if out is not None:
            returned_eps += int(out["episodes"])
    # mid-stream the deferred side lags exactly one dispatch
    assert deferred.counters["episodes"] <= sync.counters["episodes"]
    tail = deferred.flush_counted()
    assert tail is not None
    returned_eps += int(tail["episodes"])
    assert deferred.counters == sync.counters
    # one accounted ingest per dispatch under both modes (the count the
    # epoch record writes out as device_rollout_dispatches)
    assert sync.counters["ingests"] == deferred.counters["ingests"] == len(chunks)
    # every episode was also RETURNED to the caller exactly once
    assert returned_eps == sync.counters["episodes"]


def test_ingest_stats_match_records(rollout_data):
    """Ingest counters must agree with host-side counting of the same
    records (episodes finished, game/player steps)."""
    env = make_env({"env": "HungryGeese"})
    module = env.net()
    params = init_variables(module, env)["params"]
    args = rollout_data["args"]
    mesh = rollout_data["mesh"]
    fn = build_streaming_fn(VectorHungryGeese, module, 4, 16, mesh=None,
                            use_observe_mask=False)
    replay = DeviceReplay(VectorHungryGeese, module, args, mesh, 4, slots=64)
    state = VectorHungryGeese.init(4, jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    tot = {"episodes": 0, "game_steps": 0, "player_steps": 0}
    for _ in range(6):
        key, sub = jax.random.split(key)
        state, _, records = fn(params, state, None, sub)
        records = tree_map(np.asarray, jax.device_get(records))
        stats = tree_map(np.asarray, replay.ingest(records))
        assert stats["episodes"] == records["done"].sum()
        assert stats["game_steps"] == (records["active"].sum(axis=2) > 0).sum()
        assert stats["player_steps"] == records["active"].sum()
        for k in tot:
            tot[k] += int(stats[k])
    assert tot["episodes"] > 0 and tot["game_steps"] >= tot["episodes"]
    assert replay.eligible_count() > 0


# -- the record ring's storage format ----------------------------------------


def _stream_record_spec(env_name, venv, observation):
    """One step's record spec (leaves (lanes, ...)) as the streaming fn
    emits it for ``venv`` — traced, nothing runs."""
    env = make_env({"env": env_name})
    module = env.net()
    lanes = 2
    fn = build_streaming_fn(venv, module, lanes, 4, mesh=None,
                            use_observe_mask=observation)
    _, _, records = jax.eval_shape(
        fn, init_variables(module, env)["params"],
        venv.init(lanes, jax.random.PRNGKey(0)),
        module.initial_state((lanes, venv.num_players)), jax.random.PRNGKey(1),
    )
    return {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in records.items()}


def _geese_spec():
    return _stream_record_spec("HungryGeese", VectorHungryGeese, False)


def _geister_spec():
    from handyrl_tpu.envs.vector_geister import VectorGeister

    return _stream_record_spec("Geister", VectorGeister, True)


def _parallel_tictactoe_spec():
    from handyrl_tpu.envs.vector_parallel_tictactoe import VectorParallelTicTacToe

    return _stream_record_spec("ParallelTicTacToe", VectorParallelTicTacToe, False)


def _staged_spec():
    """A host-born record as ``DeviceEpisodeStage`` queues it: explicit
    ``reward``/``ret`` columns and whole observation planes, int8 under
    ``obs_int8``."""
    import random

    from handyrl_tpu.models import InferenceModel
    from handyrl_tpu.runtime.device_replay import DeviceEpisodeStage
    from handyrl_tpu.runtime.generation import Generator

    args = _args(obs_int8=True)
    random.seed(5)
    env = make_env({"env": "HungryGeese"})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env, seed=5))
    episode = None
    while episode is None:
        episode = Generator(env, args).generate(
            {p: model for p in env.players()},
            {"player": env.players(), "model_id": {p: 1 for p in env.players()}},
        )
    stage = DeviceEpisodeStage(module, args, make_mesh({"dp": 1}), n_lanes=1)
    stage.add_episode(episode)
    rec = stage._queues[0][0][0]                  # leaves (T, ...)
    assert rec["obs0"].dtype == np.int8 and {"reward", "ret"} <= set(rec)
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in rec.items()}


def _random_leaf(rng, shape, dtype):
    """Every bit pattern the dtype stores (NaNs and all): the ring must
    hand back bits, not values."""
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return rng.integers(0, 2, shape).astype(bool)
    raw = rng.integers(0, 256, tuple(shape) + (dtype.itemsize,), dtype=np.uint8)
    return raw.view(dtype).reshape(shape)


@pytest.mark.parametrize("build", [
    pytest.param(_geese_spec, id="hungry_geese"),
    pytest.param(_geister_spec, id="geister"),
    pytest.param(_parallel_tictactoe_spec, id="parallel_tictactoe"),
    pytest.param(_staged_spec, id="staged_int8_obs_reward_ret"),
])
def test_row_format_pack_unpack_is_identity(build):
    """pack -> unpack hands every leaf back bit for bit, whole rows and a
    single named field alike; a row holds every leaf on its own words and
    is a multiple of 128 words wide."""
    from handyrl_tpu.runtime.device_replay import RowFormat

    spec = build()
    fmt = RowFormat(spec)
    assert set(fmt.fields) == set(spec) - {"done"}
    assert fmt.width % 128 == 0
    spans = sorted((f.offset, f.offset + f.words) for f in fmt.fields.values())
    assert all(a[1] <= b[0] for a, b in zip(spans, spans[1:])), "fields overlap"
    assert spans[-1][1] <= fmt.width
    assert fmt.used_bytes == sum(
        int(np.prod(v.shape[1:])) * np.dtype(v.dtype).itemsize
        for k, v in spec.items() if k != "done"
    )

    rng = np.random.default_rng(0)
    lead = (3, 5)
    rec = {k: _random_leaf(rng, lead + tuple(spec[k].shape[1:]), spec[k].dtype)
           for k in fmt.fields}
    rows = jax.jit(fmt.pack)(rec)
    assert rows.shape == lead + (fmt.width,) and rows.dtype == np.int32
    back = jax.device_get(jax.jit(fmt.unpack)(rows))
    assert set(back) == set(rec)
    for k, x in rec.items():
        assert back[k].dtype == x.dtype and back[k].shape == x.shape, k
        np.testing.assert_array_equal(
            back[k].view(np.uint8), x.view(np.uint8), err_msg=k)
    one = jax.device_get(fmt.unpack(rows[0, 0], ("outcome",)))
    assert set(one) == {"outcome"}
    np.testing.assert_array_equal(
        one["outcome"].view(np.uint8), rec["outcome"][0, 0].view(np.uint8))


def _stepwise_writer(rings, fields, done, slots):
    """The plain writer the ingest must equal: one record per lane per
    step at ``g % S``, only the overwritten slot invalidated, a finished
    lane finalizing every slot of its current episode."""
    for t in range(done.shape[0]):
        g = rings["g"]
        pos = g % slots
        for k, v in fields.items():
            rings["rec"][k][:, pos] = v[t]
        rings["ep_start_g"][:, pos] = rings["cur_start_g"]
        rings["ep_end_g"][:, pos] = -1
        rings["valid"][:, pos] = False
        fin = done[t][:, None] & (rings["ep_start_g"] == rings["cur_start_g"][:, None])
        rings["ep_end_g"][fin] = g
        rings["valid"] |= fin
        rings["cur_start_g"] = np.where(done[t], g + 1, rings["cur_start_g"])
        rings["g"] = g + 1


@pytest.mark.parametrize("slots,k_steps,n_calls", [
    pytest.param(48, 32, 4, id="block_crosses_ring_end"),
    pytest.param(50, 16, 9, id="slots_not_a_multiple_of_k"),
    pytest.param(64, 16, 6, id="aligned_blocks"),
    pytest.param(12, 12, 3, id="block_as_long_as_the_ring"),
    pytest.param(10, 12, 3, id="block_longer_than_the_ring"),
])
def test_ingest_equals_stepwise_writer(slots, k_steps, n_calls):
    """Block ingests (the records written in place as packed rows, the id
    rings stepped beside them) leave the ring contents, ``ep_start_g``,
    ``ep_end_g``, ``valid``, ``cur_start_g`` and ``g`` a step-by-step numpy
    writer leaves: episodes that end inside a block, span blocks, outlive
    the ring, and lanes that finish nothing."""
    spec = _geese_spec()
    lanes = 5
    env = make_env({"env": "HungryGeese"})
    replay = DeviceReplay(VectorHungryGeese, env.net(), _args(),
                          make_mesh({"dp": 1}), lanes, slots=slots)
    want = {
        "rec": {k: np.zeros((lanes, slots) + tuple(v.shape[1:]), v.dtype)
                for k, v in spec.items() if k != "done"},
        "ep_start_g": np.full((lanes, slots), -1, np.int32),
        "ep_end_g": np.full((lanes, slots), -1, np.int32),
        "valid": np.zeros((lanes, slots), bool),
        "cur_start_g": np.zeros((lanes,), np.int32),
        "g": 0,
    }
    rng = np.random.default_rng(slots)
    # per-lane finish rates: none at all, rare (episodes outlive the ring),
    # every few steps, every step
    rate = np.array([0.0, 0.02, 0.1, 0.3, 1.0])
    for _ in range(n_calls):
        records = {
            k: _random_leaf(rng, (k_steps, lanes) + tuple(v.shape[1:]), v.dtype)
            for k, v in spec.items() if k != "done"
        }
        # the stats read these two as numbers: keep them finite
        records["outcome"] = rng.standard_normal((k_steps, lanes, 4)).astype(np.float32)
        records["done"] = rng.random((k_steps, lanes)) < rate
        replay.ingest(records)
        _stepwise_writer(want, {k: v for k, v in records.items() if k != "done"},
                         records["done"], slots)
    got = jax.device_get(replay.rings)
    assert int(got["g"]) == want["g"] == n_calls * k_steps
    for k in ("ep_start_g", "ep_end_g", "valid", "cur_start_g"):
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
    assert want["valid"].any() and not want["valid"][0].any()
    rec = jax.device_get(replay.row_format.unpack(replay.rings["rec"]))
    for k, x in want["rec"].items():
        np.testing.assert_array_equal(
            rec[k].view(np.uint8), x.view(np.uint8), err_msg=k)


def _walk_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold
    (pjit, scan, while, cond, custom calls), outermost first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)


def _ring_sized(eqn, n):
    return [v for v in eqn.outvars if getattr(v.aval, "size", 0) >= n]


def test_no_work_of_the_ring_s_size_outside_the_in_place_update(rollout_data):
    """What keeps O(ring) work from coming back unseen (the TPU's layout
    assignment is out of a CPU test's sight): in ``jit_ingest`` the only
    equations with a result as large as the record ring are the in-place
    ``dynamic_update_slice``s (and the calls that wrap them), never a loop
    that carries it; in ``jit_replay_train`` no equation's result is as
    large.  The ring here is larger than any activation of the update."""
    slots = 4096
    replay = DeviceReplay(VectorHungryGeese, rollout_data["module"],
                          rollout_data["args"], rollout_data["mesh"],
                          N_LANES, slots=slots)
    records = {
        k: np.zeros((K_STEPS, N_LANES) + f.shape, f.dtype)
        for k, f in rollout_data["replay"].row_format.fields.items()
    }
    records["done"] = np.zeros((K_STEPS, N_LANES), bool)
    replay.ingest(records)
    n = replay.rings["rec"].size
    assert n == N_LANES * slots * replay.row_format.width

    ingest = jax.make_jaxpr(replay._ingest)(replay.rings, records)
    updates = 0
    for eqn in _walk_eqns(ingest.jaxpr):
        if not _ring_sized(eqn, n):
            continue
        name = eqn.primitive.name
        assert name not in ("scan", "while"), f"{name} carries the record ring"
        if name == "dynamic_update_slice":
            updates += 1
        else:   # a call around the updates; its body was walked too
            assert any(hasattr(getattr(v, "jaxpr", v), "eqns")
                       for v in eqn.params.values()), (
                f"{name} makes a result as large as the record ring")
    assert updates == K_STEPS

    ctx = TrainContext(rollout_data["module"], rollout_data["args"],
                       rollout_data["mesh"])
    train = replay.train_fn(ctx, fused_steps=2)
    state = ctx.init_state(rollout_data["params"])
    big = [eqn.primitive.name for eqn in _walk_eqns(train.jaxpr(state).jaxpr)
           if _ring_sized(eqn, n)]
    assert not big, f"as large as the record ring: {big}"
