"""Runtime sanitizer plane (handyrl_tpu/utils/sanitizers.py).

Units pin the instrumentation itself (counting, named-site attribution,
the dispatch-lock allowlist, clean restore).  The two window tests arm
the sanitizers around REAL training surfaces:

* the ``batch_pipeline: device`` path records ZERO blocking host syncs
  across a pipeline window (batch() + train dispatches) — the PR 6
  invariant, now enforced instead of remembered — with a deliberate
  violation asserting the loud named-site report;
* a warm epoch of the real ``Learner`` streaming hot loop
  (device_replay) records ZERO XLA recompiles — one stray shape change
  silently turns a 3 ms update into a 30 s stall.

CI runs the full ``sanitizer`` marker on the 4-virtual-device CPU mesh;
the Learner window also carries ``slow`` to stay off the tier-1 budget.
"""

import threading
import time

import jax
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import init_variables
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.mesh import dispatch_serialized
from handyrl_tpu.utils.sanitizers import HostSyncSanitizer, RecompileSentinel

pytestmark = pytest.mark.sanitizer


# -- RecompileSentinel units --------------------------------------------------


def test_recompile_sentinel_quiet_on_warm_path():
    f = jax.jit(lambda x: x * 2 + 1)
    f(np.ones(7, np.float32))  # warm
    with RecompileSentinel() as sentinel:
        for _ in range(3):
            f(np.ones(7, np.float32))
    sentinel.assert_no_recompiles("warm jit loop")
    assert sentinel.count == 0


def test_recompile_sentinel_counts_and_names_the_site():
    f = jax.jit(lambda x: x * 3)
    f(np.ones(4, np.float32))
    with RecompileSentinel() as sentinel:
        f(np.ones(11, np.float32))  # new shape -> real backend compile
    assert sentinel.count >= 1
    report = sentinel.report()
    assert "test_sanitizers.py" in report, report
    with pytest.raises(AssertionError, match="compilation"):
        sentinel.assert_no_recompiles("shape drift")
    # disarmed outside the window
    f(np.ones(13, np.float32))
    assert sentinel.count == len(sentinel.events)


# -- HostSyncSanitizer units --------------------------------------------------


def test_host_sync_sanitizer_clean_on_async_dispatch():
    f = jax.jit(lambda x: x + 1)
    x = f(np.ones(3, np.float32))
    jax.block_until_ready(x)
    with HostSyncSanitizer() as sync:
        y = f(x)
        y = f(y)
    sync.assert_clean("pure async dispatch")
    jax.block_until_ready(y)  # outside the window: not recorded
    assert sync.count == 0


def test_host_sync_sanitizer_names_every_entry_point():
    x = jax.jit(lambda v: v * 2)(np.ones(3, np.float32))
    with HostSyncSanitizer() as sync:
        jax.device_get(x)
        jax.block_until_ready(x)
        float(x[0])          # ArrayImpl to-host conversion
    kinds = {e.kind for e in sync.events}
    assert "device_get" in kinds and "block_until_ready" in kinds, sync.report()
    assert "to_host" in kinds, sync.report()
    report = sync.report()
    assert "test_sanitizers.py" in report, report
    with pytest.raises(AssertionError, match="blocking host sync"):
        sync.assert_clean()
    # every patch restored
    assert jax.device_get.__module__.startswith("jax"), jax.device_get


def test_host_sync_sanitizer_allows_dispatch_lock_block():
    """The CPU backend's block INSIDE dispatch_serialized is the
    documented lock behavior (parallel/mesh.py), not a hot-loop leak —
    allowlisted by default, but still visible in the report."""
    f = jax.jit(lambda v: v + 5)
    x = f(np.ones(3, np.float32))
    with HostSyncSanitizer() as sync:
        dispatch_serialized(lambda: f(x), jax.devices()[:1])
    sync.assert_clean("locked dispatch")
    if jax.default_backend() == "cpu":
        assert sync.allowed_events, sync.report()
        assert "allowed" in sync.report()


# -- the batch_pipeline: device window ---------------------------------------


def _device_pipeline(dp=2):
    """A live DeviceBatchPipeline + TrainContext over host-born HungryGeese
    episodes (mirrors tests/test_device_stage.py's end-to-end surface)."""
    import random

    from handyrl_tpu.models.inference import InferenceModel
    from handyrl_tpu.runtime.device_batch import DeviceBatchPipeline
    from handyrl_tpu.runtime.generation import Generator
    from handyrl_tpu.runtime.replay import EpisodeStore

    random.seed(11)
    cfg = normalize_args({
        "env_args": {"env": "HungryGeese"},
        "train_args": {
            "turn_based_training": False,
            "observation": False,
            "batch_size": 4,
            "forward_steps": 8,
            "batch_pipeline": "device",
            "device_stage_lanes": dp,
            "device_stage_chunk": 4,
            "device_stage_slots": 256,
            "mesh": {"dp": dp},
        },
    })
    targs = dict(cfg["train_args"])
    targs["env"] = cfg["env_args"]
    env = make_env({"env": "HungryGeese"})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env, seed=11))
    gen = Generator(env, targs)
    gen_args = {"player": env.players(),
                "model_id": {p: 1 for p in env.players()}}
    eps = []
    while len(eps) < 8:
        ep = gen.generate({p: model for p in env.players()}, gen_args)
        if ep is not None:
            eps.append(ep)
    mesh = make_mesh({"dp": dp})
    ctx = TrainContext(module, targs, mesh)
    store = EpisodeStore(100)
    stop = threading.Event()
    pipe = DeviceBatchPipeline(targs, store, ctx, stop)
    store.extend(eps)
    pipe.start()
    # The feeder folds the store's snapshot into the rings on its own
    # thread, and from the second chunk on every ingest fetches the stats
    # of the one before (DeviceReplay._account, the deferred fetch: a
    # device_get by design, once a chunk, never on the consumer's thread).
    # batch() can return after the first chunk, so a window armed then
    # caught the feeder's later fetches whenever the feeder was slow (six
    # busy workers).  No episode arrives after the snapshot here: wait for
    # the feeder to book its one pass (put_s is written after its flush).
    deadline = time.monotonic() + 120.0
    while not (pipe.stats()["episodes_staged"] == len(eps) and pipe.stats()["put_s"] > 0):
        assert time.monotonic() < deadline and not stop.is_set(), pipe.stats()
        time.sleep(0.02)
    state = ctx.init_state(init_variables(module, env, seed=11)["params"])
    return pipe, ctx, state, stop


def test_device_pipeline_window_is_host_sync_free():
    """PR 6's invariant, armed: across a pipeline window on the
    batch_pipeline: device path — batch() sampling dispatches plus real
    train-step dispatches — the ONLY blocking transfers are the
    allowlisted dispatch-lock blocks (CPU backend).  A deliberate
    violation inside the same window produces the loud named-site
    report."""
    pipe, ctx, state, stop = _device_pipeline(dp=2)
    try:
        # warm everything outside the window: first batch (ring init +
        # sampler jit) and first train dispatch (train-step jit)
        batch = pipe.batch()
        assert batch is not None
        state, _ = ctx.train_step(state, batch, 1e-5)

        with HostSyncSanitizer() as sync, RecompileSentinel() as sentinel:
            for _ in range(4):
                batch = pipe.batch()
                assert batch is not None
                state, metrics = ctx.train_step(state, batch, 1e-5)
        sync.assert_clean("batch_pipeline: device window")
        sentinel.assert_no_recompiles("batch_pipeline: device window")

        # negative: a stray host conversion in the same window is caught
        # and NAMED (file:line of this test, not a vague count)
        with HostSyncSanitizer() as sync:
            batch = pipe.batch()
            np.asarray(jax.device_get(batch["action"]))  # deliberate leak
        assert sync.events, sync.report()
        report = sync.report()
        assert "test_sanitizers.py" in report, report
        with pytest.raises(AssertionError, match="test_sanitizers.py"):
            sync.assert_clean("deliberate violation")
    finally:
        stop.set()
        pipe.stop()


# -- the Learner streaming hot loop ------------------------------------------


@pytest.mark.slow
def test_learner_streaming_epoch_has_zero_recompiles(tmp_path, monkeypatch):
    """Acceptance gate: a POST-WARM-UP epoch of the real Learner
    streaming hot loop (device_replay on the multi-device CPU mesh)
    triggers zero XLA compilations — rollout dispatches, ring ingest,
    fused sample+train, param publish and the epoch boundary all hit
    warm executables.  The sentinel window is aligned to model-epoch
    boundaries (epoch 2 -> 3), after two full epochs warmed every path
    including the eval workers' inference buckets."""
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
            "update_episodes": 30,
            "maximum_episodes": 1000,
            "epochs": 4,
            "eval_rate": 0.0,
            "device_rollout_games": 8,
            "device_replay": True,
            "device_replay_slots": 256,
            "device_replay_k_steps": 16,
            "mesh": {"dp": 4},
            "worker": {"num_parallel": 1},
        },
    })
    learner = Learner(cfg)
    thread = threading.Thread(target=learner.run, daemon=True)
    thread.start()

    def wait_for_epoch(n, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if learner.model_epoch >= n:
                return True
            if not thread.is_alive():
                return learner.model_epoch >= n
            time.sleep(0.2)
        return False

    assert wait_for_epoch(2, 600), (
        f"warm-up never reached epoch 2 (at {learner.model_epoch})"
    )
    with RecompileSentinel() as sentinel:
        assert wait_for_epoch(3, 600), (
            f"window never reached epoch 3 (at {learner.model_epoch})"
        )
    thread.join(timeout=600)
    sentinel.assert_no_recompiles("streaming hot loop epoch 2->3")
    assert learner.trainer.steps > 0
