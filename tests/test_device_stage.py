"""DeviceEpisodeStage / DeviceBatchPipeline (host-bypass assembly) tests.

The bar (ISSUE 6 acceptance, same as tests/test_device_replay.py): a
window sampled and assembled ON DEVICE from staged host-born episodes
must equal, key by key, the batch the host path (EpisodeStore window ->
make_batch) builds for the SAME episode, window start, and target player.
Both paths consume identical generator episodes, so every difference is
an assembly bug, not sampling noise.
"""

import random
import threading

import jax
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import InferenceModel, init_variables
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.runtime import codec
from handyrl_tpu.runtime.batch import make_batch
from handyrl_tpu.runtime.device_batch import DeviceBatchPipeline
from handyrl_tpu.runtime.device_replay import DeviceEpisodeStage
from handyrl_tpu.runtime.generation import Generator
from handyrl_tpu.runtime.replay import EpisodeStore
from handyrl_tpu.utils import tree_map

pytestmark = pytest.mark.pipeline


def _targs(env="HungryGeese", **over):
    base = {"mesh": {"dp": 1}}
    base.update(over)
    cfg = normalize_args({"env_args": {"env": env}, "train_args": base})
    args = dict(cfg["train_args"])
    args["env"] = cfg["env_args"]
    return args


def _gen_episodes(env_name, n, targs, seed=0):
    random.seed(seed)
    env = make_env({"env": env_name})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env, seed=seed))
    gen = Generator(env, targs)
    models = {p: model for p in env.players()}
    gen_args = {"player": env.players(), "model_id": {p: 1 for p in env.players()}}
    eps = []
    while len(eps) < n:
        ep = gen.generate(models, gen_args)
        if ep is not None:
            eps.append(ep)
    return env, module, eps


def _stage_with_episodes(env_name="HungryGeese", n=40, lanes=4, chunk=8,
                         slots=256, **over):
    over.setdefault("batch_size", 8)
    over.setdefault("forward_steps", 8)
    targs = _targs(env_name, **over)
    env, module, eps = _gen_episodes(env_name, n, targs)
    mesh = make_mesh({"dp": 1})
    stage = DeviceEpisodeStage(
        module, targs, mesh, n_lanes=lanes, slots=slots, chunk_steps=chunk,
        track_episodes=True,
    )
    for ep in eps:
        stage.add_episode(ep)
    stage.flush()
    stage.drain()
    return {"stage": stage, "episodes": eps, "args": targs,
            "module": module, "env": env, "mesh": mesh}


def _host_window(ep, train_start, args):
    """The exact sample_window dict (replay.py) for a forced train_start."""
    fwd, cs = args["forward_steps"], args["compress_steps"]
    steps = ep["steps"]
    start = max(0, train_start - args["burn_in_steps"])
    end = min(train_start + fwd, steps)
    first_block = start // cs
    last_block = (end - 1) // cs + 1
    return {
        "args": ep["args"],
        "outcome": np.asarray(
            [ep["outcome"][p] for p in ep["players"]], np.float32
        ),
        "players": ep["players"],
        "blocks": ep["blocks"][first_block:last_block],
        "base": first_block * cs,
        "start": start,
        "end": end,
        "train_start": train_start,
        "total": steps,
    }


def _check_windows(data, monkeypatch, n, seed=3):
    """Key-by-key equality of stage-assembled windows vs make_batch on the
    same (episode, train_start, target player) — test_device_replay's bar,
    mapped through the stage's lane-span ledger."""
    stage, args = data["stage"], data["args"]
    replay = stage.replay
    S = stage.slots
    G = int(jax.device_get(replay.rings["g"]))

    batch, info = replay.sample(jax.random.PRNGKey(seed), n, with_info=True)
    batch = tree_map(np.asarray, batch)

    for i in range(n):
        lane, slot, player = (
            int(info["lane"][i]), int(info["slot"][i]), int(info["player"][i])
        )
        gs0 = G - 1 - ((G - 1 - slot) % S)     # global step held by the slot
        hits = [s for s in stage.spans[lane] if s[0] <= gs0 <= s[1]]
        assert hits, f"sampled slot maps to no staged episode (lane {lane}, g {gs0})"
        g0, g1, ep = hits[0]
        train_start = gs0 - g0
        assert train_start <= max(0, ep["steps"] - args["forward_steps"])

        if player >= 0:   # ff mode: one target player per window
            monkeypatch.setattr(
                "handyrl_tpu.runtime.batch.random.randrange", lambda _n: player
            )
        host = make_batch([_host_window(ep, train_start, args)], args)

        for key in host:
            host_leaves = jax.tree.leaves(host[key])
            got_leaves = jax.tree.leaves(batch[key])
            assert len(host_leaves) == len(got_leaves), key
            for hl, gl in zip(host_leaves, got_leaves):
                np.testing.assert_allclose(
                    gl[i : i + 1], hl, atol=1e-6, err_msg=f"{key} row {i}"
                )


def test_stage_ff_windows_match_make_batch(monkeypatch):
    """North-star configuration: HungryGeese episodes staged into rings,
    device-assembled ff windows equal make_batch key by key."""
    data = _stage_with_episodes(
        "HungryGeese", n=40, turn_based_training=False, observation=False,
    )
    assert data["stage"].replay.eligible_count() > 0
    _check_windows(data, monkeypatch, n=32)


def test_stage_turn_windows_match_make_batch(monkeypatch):
    """Turn mode (all-player windows + burn-in): TicTacToe episodes with
    observation: true through the same parity bar."""
    data = _stage_with_episodes(
        "TicTacToe", n=16, lanes=2, chunk=8, slots=64,
        turn_based_training=True, observation=True,
        batch_size=4, forward_steps=4, burn_in_steps=2,
    )
    assert data["stage"].replay.eligible_count() > 0
    _check_windows(data, monkeypatch, n=24)


def test_stage_blob_path_matches_decoded_path():
    """add_blob (the wire-codec bytes EpisodeStore mirrors to batcher
    children) must stage bit-identically to add_episode."""
    targs = _targs("TicTacToe", batch_size=4, forward_steps=8,
                   turn_based_training=True, observation=True)
    _, module, eps = _gen_episodes("TicTacToe", 6, targs)
    mesh = make_mesh({"dp": 1})
    stages = []
    for use_blob in (False, True):
        stage = DeviceEpisodeStage(module, targs, mesh, n_lanes=2,
                                   slots=64, chunk_steps=8)
        for ep in eps:
            if use_blob:
                stage.add_blob(codec.dumps(ep))
            else:
                stage.add_episode(ep)
        stage.flush()
        stage.drain()
        stages.append(stage)
    a, b = stages
    assert a.episodes_staged == b.episodes_staged == len(eps)
    assert a.chunks_flushed == b.chunks_flushed > 0
    key = jax.random.PRNGKey(9)
    ba = tree_map(np.asarray, a.replay.sample(key, 8))
    bb = tree_map(np.asarray, b.replay.sample(key, 8))
    for la, lb in zip(jax.tree.leaves(ba), jax.tree.leaves(bb)):
        np.testing.assert_array_equal(la, lb)


def test_stage_lane_balancing_and_spans():
    """Episodes land on the shortest lane; spans are contiguous and
    non-overlapping per lane; staged totals add up."""
    data = _stage_with_episodes(
        "HungryGeese", n=40, turn_based_training=False, observation=False,
    )
    stage = data["stage"]
    assert stage.episodes_staged == len(data["episodes"])
    assert stage.steps_staged == sum(e["steps"] for e in data["episodes"])
    for lane in range(stage.n_lanes):
        pos = 0
        for g0, g1, ep in stage.spans[lane]:
            assert g0 == pos and g1 == pos + ep["steps"] - 1
            pos = g1 + 1
        assert pos == stage._qtotal[lane]
    # greedy balancing: no lane is more than one episode's length ahead
    longest = max(e["steps"] for e in data["episodes"])
    assert max(stage._qtotal) - min(stage._qtotal) <= longest


def test_stage_mode_validation():
    targs = _targs("TicTacToe", turn_based_training=True, observation=False)
    env = make_env({"env": "TicTacToe"})
    mesh = make_mesh({"dp": 1})
    with pytest.raises(ValueError, match="observation"):
        DeviceEpisodeStage(env.net(), targs, mesh)
    targs = _targs("TicTacToe", turn_based_training=False, burn_in_steps=0)
    with pytest.raises(ValueError, match="recurrent"):
        DeviceEpisodeStage(
            make_env({"env": "Geister"}).net(), targs, mesh
        )


def test_device_pipeline_feeds_trainer_batches():
    """The full pipeline surface: store-subscribed episodes upload once,
    batch() returns device-resident dp-sharded batches the train step
    consumes — and the per-stage stats vocabulary stays intact."""
    targs = _targs("HungryGeese", batch_size=4, forward_steps=8,
                   turn_based_training=False, observation=False,
                   device_stage_lanes=2, device_stage_chunk=4,
                   device_stage_slots=256)
    env, module, eps = _gen_episodes("HungryGeese", 8, targs)
    store = EpisodeStore(100)
    mesh = make_mesh({"dp": 1})
    ctx = TrainContext(module, targs, mesh)
    stop = threading.Event()
    pipe = DeviceBatchPipeline(targs, store, ctx, stop)
    store.extend(eps[:4])
    pipe.start()
    store.extend(eps[4:])    # live feed rides the subscription
    try:
        batch = pipe.batch()
        assert batch is not None
        assert isinstance(batch["action"], jax.Array)
        B, T = targs["batch_size"], targs["forward_steps"]
        assert batch["action"].shape[:2] == (B, T)
        # the batch feeds the real train step with no host round-trip
        state = ctx.init_state(init_variables(module, env)["params"])
        state, metrics = ctx.train_step(state, batch, 1e-5)
        assert np.isfinite(float(jax.device_get(metrics["total"])))
        stats = pipe.stats()
        assert stats["mode"] == "device"
        assert stats["batches"] >= 1
        assert stats["episodes_staged"] == len(eps)
        for key in ("sample_s", "assemble_s", "ready_wait_s", "put_s"):
            assert key in stats
    finally:
        stop.set()
        pipe.stop()


def test_make_pipeline_selects_device_mode():
    from handyrl_tpu.runtime.trainer import BatchPipeline, make_pipeline

    targs = _targs("HungryGeese", batch_size=4, forward_steps=8,
                   turn_based_training=False, observation=False,
                   batch_pipeline="device")
    env, module, _ = _gen_episodes("HungryGeese", 1, targs)
    ctx = TrainContext(module, targs, make_mesh({"dp": 1}))
    store = EpisodeStore(10)
    assert isinstance(make_pipeline(targs, store, ctx), DeviceBatchPipeline)
    # a stage-mode misconfiguration raises — a run never trains through
    # another pipeline than the one it was asked for (recurrent net in
    # ff mode cannot stage on device)
    bad = _targs("Geister", batch_size=4, forward_steps=8,
                 turn_based_training=False, batch_pipeline="device")
    genv = make_env({"env": "Geister"})
    gctx = TrainContext(genv.net(), dict(bad, turn_based_training=True,
                                         observation=True),
                        make_mesh({"dp": 1}))
    with pytest.raises(ValueError, match="recurrent net"):
        make_pipeline(bad, store, gctx)


def test_config_validates_device_stage_knobs():
    with pytest.raises(ValueError, match="device_replay"):
        _targs(batch_pipeline="device", device_replay=True,
               device_rollout_games=8, turn_based_training=False)
    with pytest.raises(ValueError, match="device_stage_slots"):
        _targs(batch_pipeline="device", device_stage_slots=8,
               forward_steps=16, turn_based_training=False)
    with pytest.raises(ValueError, match="device_stage_lanes"):
        _targs(batch_pipeline="device", device_stage_lanes=0,
               turn_based_training=False)
    assert _targs(batch_pipeline="device",
                  turn_based_training=False)["device_stage_chunk"] == 64


@pytest.mark.slow  # full Learner stack; the CI pipeline step still runs it
def test_learner_device_pipeline_end_to_end(tmp_path, monkeypatch):
    """Full --train stack with batch_pipeline: device — device rollouts
    feed HOST episodes into the store, the stage uploads them once, and
    training consumes device-assembled windows: epochs advance,
    checkpoints land, and the metrics record the live 'device' pipeline
    plus the warm-up wait split out of input_wait_frac."""
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
            "minimum_episodes": 8,
            "update_episodes": 24,
            "maximum_episodes": 1000,
            "epochs": 1,
            "eval_rate": 0.0,
            "device_rollout_games": 8,
            "batch_pipeline": "device",
            "device_stage_lanes": 4,
            "device_stage_chunk": 16,
            "device_stage_slots": 256,
            "mesh": {"dp": 1},
            "worker": {"num_parallel": 1},
        },
    })
    learner = Learner(cfg)
    learner.run()

    records = [json.loads(l) for l in open("metrics.jsonl")]
    assert records, "no metrics were written"
    assert records[-1]["steps"] > 0, "no SGD updates ran"
    assert any(r.get("pipeline") == "device" for r in records)
    trained = [r for r in records if "input_wait_frac" in r]
    assert trained, "no trained epoch recorded input_wait_frac"
    # the run's first batch wait was split out of the starvation metric
    assert any("input_wait_warmup_s" in r for r in trained)
    assert os.path.exists("models/latest.ckpt")
