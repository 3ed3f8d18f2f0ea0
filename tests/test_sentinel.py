"""Self-healing run plane (docs/fault_tolerance.md): divergence sentinel,
plane watchdog, preemption-safe drain.

The contract, pinned on the virtual CPU mesh:

* The compiled train step SKIPS any update whose loss / grad global-norm
  / lr is nonfinite (the flag rides back with the existing metrics — no
  extra host sync), so a single NaN can never poison params or Adam
  moments; with ``sentinel: false`` the step is bit-identical to the
  pre-sentinel one and the poison lands (the old failure mode).
* The host-side loss-spike EMA detector extends the same
  consecutive-bad streak, and the streak escalates to a rollback onto
  the newest VERIFIED manifest checkpoint.
* The plane watchdog restarts a dead/stalled rollout thread up to
  ``plane_max_restarts``, then degrades split -> fused loudly.
* SIGTERM/SIGINT drain the run into a final manifest-verified
  checkpoint and exit resumable (75), composing with ``restart_epoch:
  -1`` for a full preempt -> resume loop.

Fast tests run in the tier-1 sweep; the injection-driven end-to-ends are
marked ``slow`` and run standalone in CI under ``-m sentinel`` on the
4-virtual-device mesh.
"""

import json
import os
import random
import threading
import time

import jax
import numpy as np
import pytest

import handyrl_tpu.runtime.checkpoint as cp
from handyrl_tpu.config import normalize_args
from handyrl_tpu.runtime import faults
from handyrl_tpu.runtime.trainer import SENTINEL_EVENT_KEYS, Trainer
from handyrl_tpu.utils import read_metrics

pytestmark = pytest.mark.sentinel

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >= 4 (virtual) devices"
)


# ------------------------------------------------------------ injection env


def test_fault_env_parsing(monkeypatch):
    for var in ("HANDYRL_FAULT_NAN_AT_STEP", "HANDYRL_FAULT_WEDGE_ROLLOUT",
                "HANDYRL_FAULT_SIGTERM_AT_STEP"):
        monkeypatch.delenv(var, raising=False)
    assert faults.nan_window() is None
    assert faults.wedge_rollout() is None
    assert faults.sigterm_at_step() is None

    monkeypatch.setenv("HANDYRL_FAULT_NAN_AT_STEP", "7")
    assert faults.nan_window() == (7, 1)
    monkeypatch.setenv("HANDYRL_FAULT_NAN_AT_STEP", "7:3")
    assert faults.nan_window() == (7, 3)

    monkeypatch.setenv("HANDYRL_FAULT_WEDGE_ROLLOUT", "2")
    assert faults.wedge_rollout() == (2, False)
    monkeypatch.setenv("HANDYRL_FAULT_WEDGE_ROLLOUT", "2:all")
    assert faults.wedge_rollout() == (2, True)
    # a typo'd injection must raise, not silently not-inject (a fake
    # green e2e is worse than a red one)
    monkeypatch.setenv("HANDYRL_FAULT_WEDGE_ROLLOUT", "2:first")
    with pytest.raises(ValueError):
        faults.wedge_rollout()

    monkeypatch.setenv("HANDYRL_FAULT_SIGTERM_AT_STEP", "11")
    assert faults.sigterm_at_step() == 11


# ------------------------------------------------- crash-safe metrics.jsonl


def test_read_metrics_tolerates_truncated_tail_only(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    good = [{"epoch": 1, "steps": 10}, {"epoch": 2, "steps": 20}]
    with open(path, "w") as f:
        for rec in good:
            f.write(json.dumps(rec) + "\n")
        f.write('{"epoch": 3, "st')  # killed mid-append

    assert read_metrics(path) == good
    # strict mode surfaces the truncation instead of hiding it
    with pytest.raises(ValueError):
        read_metrics(path, strict=True)

    # mid-file corruption is NOT the append protocol's signature: raise
    bad = str(tmp_path / "corrupt.jsonl")
    with open(bad, "w") as f:
        f.write('{"epoch": 1}\n')
        f.write("garbage\n")
        f.write('{"epoch": 2}\n')
    with pytest.raises(ValueError):
        read_metrics(bad)


def test_write_metrics_is_one_flushed_line_per_record(tmp_path):
    """One write() + flush + fsync per record: re-reading right after the
    call must see the full line (no buffered half-records a kill could
    truncate beyond the final line)."""
    from handyrl_tpu.runtime.learner import Learner

    path = str(tmp_path / "metrics.jsonl")

    class Stub:
        args = {"metrics_path": path}
        _repair_metrics_tail = Learner._repair_metrics_tail

    for epoch in (1, 2):
        Learner._write_metrics(Stub(), {"epoch": epoch, "win_rate": None})
        last = read_metrics(path)[-1]
        # the single timestamp seam stamps both clocks onto every record
        assert last.pop("ts") > 0 and last.pop("t_mono") > 0
        assert last == {"epoch": epoch, "win_rate": None}
    assert len(read_metrics(path)) == 2


def test_resumed_run_repairs_truncated_metrics_tail(tmp_path):
    """A relaunch after a kill mid-append must DROP the half-written tail
    before appending: gluing the resumed run's first record onto it would
    turn tolerated end-of-file truncation into mid-file corruption every
    reader refuses."""
    from handyrl_tpu.runtime.learner import Learner

    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"epoch": 1}) + "\n")
        f.write('{"epoch": 2, "st')  # the kill window

    class Stub:
        args = {"metrics_path": path}
        _repair_metrics_tail = Learner._repair_metrics_tail

    stub = Stub()  # fresh process: tail check re-arms
    Learner._write_metrics(stub, {"epoch": 2})
    Learner._write_metrics(stub, {"epoch": 3})
    # strict: NO invalid line survives anywhere in the file (the appended
    # records additionally carry the ts/t_mono timestamp seam)
    records = read_metrics(path, strict=True)
    assert [r["epoch"] for r in records] == [1, 2, 3]
    assert all("ts" in r and "t_mono" in r for r in records[1:])


# ----------------------------------------------------- in-step finite check


def _train_setup(sentinel: bool, host_batches: int = 0):
    """(context, state, device batch); with ``host_batches`` the third is
    that many host batches instead, for the caller to spoil and to put."""
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.runtime.batch import make_batch
    from handyrl_tpu.runtime.generation import Generator
    from handyrl_tpu.runtime.replay import EpisodeStore

    targs = normalize_args(
        {
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                "batch_size": 8,
                "forward_steps": 8,
                "sentinel": sentinel,
            },
        }
    )["train_args"]
    random.seed(0)
    env = make_env({"env": "TicTacToe"})
    module = env.net()
    variables = init_variables(module, env, seed=0)
    model = InferenceModel(module, variables)
    gen = Generator(env, targs)
    models = {p: model for p in env.players()}
    gargs = {"player": env.players(), "model_id": {p: 1 for p in env.players()}}
    store = EpisodeStore(100)
    while len(store) < 10:
        ep = gen.generate(models, gargs)
        if ep is not None:
            store.extend([ep])
    mesh = make_mesh({"dp": -1})
    ctx = TrainContext(module, targs, mesh)
    state = ctx.init_state(variables["params"])
    batches = [
        make_batch([store.sample_window(8, 0, 4) for _ in range(8)], targs)
        for _ in range(max(host_batches, 1))
    ]
    return ctx, state, batches if host_batches else ctx.put_batch(batches[0])


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def test_in_step_sentinel_skips_nonfinite_update():
    """A NaN lr (the injection's poison vector — same flag path as a NaN
    loss or grad) must leave params AND Adam moments bit-identical, zero
    the step's loss contributions, and raise the sentinel_bad flag."""
    ctx, state, batch = _train_setup(sentinel=True)
    state1, m1 = ctx.train_step(state, batch, 1e-5)
    assert float(jax.device_get(m1["sentinel_bad"])) == 0.0
    # the step donates its input state: snapshot to host BEFORE stepping on
    host1 = jax.device_get(state1)

    state2, m2 = ctx.train_step(state1, batch, float("nan"))
    host2 = jax.device_get(state2)
    assert float(jax.device_get(m2["sentinel_bad"])) == 1.0
    # the skipped step contributes nothing to the epoch's loss averages
    assert float(jax.device_get(m2["total"])) == 0.0
    assert float(jax.device_get(m2["dcnt"])) == 0.0
    # params and optimizer state byte-identical to before the bad step
    for a, b in zip(_leaves(host1["params"]), _leaves(host2["params"])):
        assert np.array_equal(a, b)
    for a, b in zip(_leaves(host1["opt_state"]), _leaves(host2["opt_state"])):
        assert np.array_equal(a, b)
    # the step counter stays monotone (lr schedule / publish versions)
    assert int(host2["steps"]) == int(host1["steps"]) + 1

    # ... and the run keeps learning afterwards: the next finite step
    # moves params again
    state3, m3 = ctx.train_step(state2, batch, 1e-5)
    host3 = jax.device_get(state3)
    assert float(jax.device_get(m3["sentinel_bad"])) == 0.0
    assert np.isfinite(float(jax.device_get(m3["total"])))
    moved = any(
        not np.array_equal(a, b)
        for a, b in zip(_leaves(host2["params"]), _leaves(host3["params"]))
    )
    assert moved


def _spoiled(host_batch):
    """The batch with every observation NaN: the loss and every gradient
    leaf come out non-finite, whatever the lr."""
    return dict(host_batch, observation=jax.tree.map(
        lambda x: np.full_like(x, np.nan), host_batch["observation"]))


def test_in_step_sentinel_skips_a_nonfinite_batch():
    """The NaN comes from the data and not the lr: the update's arithmetic
    runs on NaN gradients (it is straight-line code of the step) and the
    select drops all of it: params and every leaf of the optimizer state,
    Adam's count included, stay byte-identical."""
    ctx, state, (good,) = _train_setup(sentinel=True, host_batches=1)
    state1, _ = ctx.train_step(state, ctx.put_batch(good), 1e-5)
    host1 = jax.device_get(state1)
    assert [int(x) for x in _leaves(host1["opt_state"]) if x.ndim == 0] == [1]

    state2, m2 = ctx.train_step(state1, ctx.put_batch(_spoiled(good)), 1e-5)
    host2, m2 = jax.device_get((state2, m2))
    assert float(m2["sentinel_bad"]) == 1.0
    assert float(m2["total"]) == 0.0 and float(m2["dcnt"]) == 0.0
    for key in ("params", "opt_state"):
        before, after = _leaves(host1[key]), _leaves(host2[key])
        assert len(before) == len(after) > 0
        for a, b in zip(before, after):
            assert np.isfinite(b).all() and np.array_equal(a, b)
    assert int(host2["steps"]) == int(host1["steps"]) + 1


def test_fused_steps_skip_the_bad_batch_in_the_middle():
    """``train_steps`` over three stacked batches, the middle one spoiled,
    ends where the two good ones alone end: the scan's carry takes the
    select's old leaves through the bad step."""
    ctx, state, (first, second) = _train_setup(sentinel=True, host_batches=2)
    state3, m3 = ctx.train_steps(
        state, ctx.put_batches([first, _spoiled(first), second]), 1e-3)
    host3, m3 = jax.device_get((state3, m3))
    ctx, state, _ = _train_setup(sentinel=True)
    state2, m2 = ctx.train_steps(state, ctx.put_batches([first, second]), 1e-3)
    host2, m2 = jax.device_get((state2, m2))

    assert float(m3["sentinel_bad"]) == 1.0 and float(m2["sentinel_bad"]) == 0.0
    assert (int(host3["steps"]), int(host2["steps"])) == (3, 2)
    for key in ("total", "dcnt"):
        np.testing.assert_allclose(m3[key], m2[key], rtol=1e-5)
    for key in ("params", "opt_state"):
        for a, b in zip(_leaves(host3[key]), _leaves(host2[key])):
            # two scans of one body; Adam's count is an integer and exact
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            if a.ndim == 0:
                assert int(a) == int(b) == 2


def test_sentinel_off_reproduces_the_poisoning_failure_mode():
    """``sentinel: false`` is the pre-sentinel step: a NaN lr lands in the
    params forever (why the sentinel defaults on)."""
    ctx, state, batch = _train_setup(sentinel=False)
    state1, m1 = ctx.train_step(state, batch, float("nan"))
    assert "sentinel_bad" not in m1
    poisoned = any(
        not np.isfinite(leaf).all() for leaf in _leaves(state1["params"])
    )
    assert poisoned


def test_sentinel_happy_path_bit_identical_to_off():
    """With finite inputs the guarded step must produce byte-identical
    params to the unguarded one — the sentinel costs a predicate and a
    select, never a different numeric path."""
    ctx_on, state_on, batch_on = _train_setup(sentinel=True)
    ctx_off, state_off, batch_off = _train_setup(sentinel=False)
    s_on, _ = ctx_on.train_step(state_on, batch_on, 1e-5)
    s_off, _ = ctx_off.train_step(state_off, batch_off, 1e-5)
    for a, b in zip(_leaves(s_on["params"]), _leaves(s_off["params"])):
        assert np.array_equal(a, b)


# ------------------------------------------------ host spike detector unit


def _bare_trainer(rollback_after=3, spike_factor=10.0, fused=1):
    t = object.__new__(Trainer)
    t.sentinel = True
    t.sentinel_rollback_after = rollback_after
    t._spike_factor = spike_factor
    t._loss_ema_decay = 0.9
    t._loss_ema = None
    t._sentinel_streak = 0
    t.sentinel_events = {k: 0 for k in SENTINEL_EVENT_KEYS}
    t.fused = fused
    t.cadence = None  # single-process: no multi-host rollback broadcasts
    t.rolled = 0
    t._sentinel_rollback = lambda: setattr(t, "rolled", t.rolled + 1) or _reset(t)
    return t


def _reset(t):
    t._sentinel_streak = 0
    t._loss_ema = None


def _m(total=1.0, dcnt=1.0, bad=0.0):
    return {"total": total, "dcnt": dcnt, "sentinel_bad": bad}


def test_spike_detector_streak_escalates_and_resets():
    t = _bare_trainer(rollback_after=3)
    # warm the EMA with clean steps
    t._sentinel_account([_m(1.0), _m(1.1), _m(0.9)])
    assert t._sentinel_streak == 0 and t.rolled == 0
    # two spikes + one in-step skip = streak 3 -> rollback
    t._sentinel_account([_m(50.0), _m(60.0), _m(bad=1.0)])
    assert t.rolled == 1
    assert t.sentinel_events["sentinel_spike_steps"] == 2
    assert t.sentinel_events["sentinel_skipped_steps"] == 1

    # a clean step RESETS the streak: isolated spikes never escalate
    t2 = _bare_trainer(rollback_after=3)
    t2._sentinel_account([_m(1.0), _m(1.0)])
    t2._sentinel_account([_m(50.0), _m(1.0), _m(50.0), _m(1.0), _m(50.0)])
    assert t2.rolled == 0
    assert t2.sentinel_events["sentinel_spike_steps"] == 3


def test_spike_detector_ema_ignores_bad_steps():
    """A diverging loss must not drag the EMA baseline up: after a run of
    spikes the detector still judges against the pre-spike EMA."""
    t = _bare_trainer(rollback_after=100)
    t._sentinel_account([_m(1.0), _m(1.0)])
    ema0 = t._loss_ema
    t._sentinel_account([_m(500.0), _m(900.0)])
    assert t._loss_ema == ema0  # spikes never fed the EMA
    # a loss 10x the REAL baseline still counts as a spike
    t._sentinel_account([_m(20.0)])
    assert t.sentinel_events["sentinel_spike_steps"] == 3


def test_rollback_without_verified_snapshot_keeps_params(tmp_path):
    """The escalation with nothing to roll back to must not crash: the
    streak resets and the run continues (the in-step skip already
    suppressed the bad updates)."""
    t = _bare_trainer(rollback_after=1)
    t._sentinel_rollback = Trainer._sentinel_rollback.__get__(t)
    t.args = {"model_dir": str(tmp_path / "models"), "seed": 0}
    t._sentinel_streak = 5
    t._sentinel_rollback()  # no manifest at all
    assert t._sentinel_streak == 0
    assert t.sentinel_events["sentinel_rollbacks"] == 0


# -------------------------------------------------- watchdog escalation


def test_watchdog_restarts_then_degrades():
    """A dead rollout thread burns the restart budget, then a split-plane
    run degrades to fused and the watchdog keeps supervising the new
    plane (returning only once it is fused AND out of budget)."""
    from handyrl_tpu.runtime.rollout_plane import WATCHDOG_EVENT_KEYS, RolloutPlane

    lrn = object.__new__(RolloutPlane)
    lrn.args = {"plane_stall_timeout": 0.2, "plane_max_restarts": 1,
                "plane_param_lag_bound": 0}
    lrn._live = lambda: True
    lrn._halt = threading.Event()
    lrn.topology = "split"
    lrn._param_cache = None
    lrn.events = {k: 0 for k in WATCHDOG_EVENT_KEYS}
    lrn._progress_t = time.monotonic()
    calls = {"restarts": 0, "degrades": 0}

    dead = threading.Thread(target=lambda: None)
    dead.start()
    dead.join()
    lrn.thread = dead

    def fake_restart():
        calls["restarts"] += 1
        lrn.events["plane_watchdog_restarts"] += 1
        lrn._progress_t = time.monotonic()
        return dead  # the restarted thread dies again immediately

    def fake_degrade():
        calls["degrades"] += 1
        lrn.events["plane_watchdog_degraded"] = 1
        lrn.topology = "fused"  # the real degrade flips the topology

    lrn._start_thread = fake_restart
    lrn._degrade_to_fused = fake_degrade

    t = threading.Thread(target=lrn._watchdog_loop, daemon=True)
    t.start()
    t.join(timeout=30.0)
    assert not t.is_alive(), "watchdog never escalated through its ladder"
    assert calls == {"restarts": 1, "degrades": 1}
    assert lrn.events["plane_watchdog_stalls"] >= 2
    assert lrn.events["plane_watchdog_degraded"] == 1


def test_watchdog_stall_waits_for_first_dispatch():
    """First-dispatch silence is jit compile time, not a stall: an ALIVE
    thread that has not completed a dispatch yet must never trip the
    stall detector (restarting mid-compile would burn the whole budget on
    a healthy warm-up); the first completed dispatch arms it."""
    from handyrl_tpu.runtime.rollout_plane import WATCHDOG_EVENT_KEYS, RolloutPlane

    lrn = object.__new__(RolloutPlane)
    lrn.args = {"plane_stall_timeout": 0.15, "plane_max_restarts": 5,
                "plane_param_lag_bound": 0}
    lrn._live = lambda: True
    lrn._halt = threading.Event()
    lrn.topology = "fused"
    lrn._param_cache = None
    lrn.events = {k: 0 for k in WATCHDOG_EVENT_KEYS}
    lrn._progress_t = time.monotonic()
    lrn._dispatched = False      # "still compiling"
    stop = threading.Event()
    alive = threading.Thread(target=stop.wait, daemon=True)
    alive.start()
    lrn.thread = alive
    lrn._start_thread = lambda: (_ for _ in ()).throw(
        AssertionError("restarted a compiling thread")
    )

    t = threading.Thread(target=lrn._watchdog_loop, daemon=True)
    t.start()
    try:
        time.sleep(0.6)  # 4x the timeout with no beat: still no stall
        assert lrn.events["plane_watchdog_stalls"] == 0
        # first dispatch lands -> detection arms -> the next silent
        # window IS a stall
        lrn._start_thread = lambda: setattr(
            lrn, "_progress_t", time.monotonic()
        )
        lrn._dispatched = True
        deadline = time.monotonic() + 10.0
        while (
            not lrn.events["plane_watchdog_stalls"]
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert lrn.events["plane_watchdog_stalls"] >= 1
    finally:
        lrn._halt.set()
        stop.set()
        t.join(timeout=10.0)


# ------------------------------------------------------- config validation


def test_config_validates_sentinel_knobs():
    def check(**over):
        return normalize_args(
            {"env_args": {"env": "TicTacToe"}, "train_args": over}
        )

    check(sentinel=False)  # knob exists and validates
    with pytest.raises(ValueError):
        check(sentinel_rollback_after=0)
    with pytest.raises(ValueError):
        check(sentinel_spike_factor=1.0)
    with pytest.raises(ValueError):
        check(sentinel_loss_ema_decay=1.0)
    with pytest.raises(ValueError):
        check(plane_stall_timeout=0)
    with pytest.raises(ValueError):
        check(plane_max_restarts=-1)
    with pytest.raises(ValueError):
        check(plane_param_lag_bound=-1)
    with pytest.raises(ValueError):
        check(drain_deadline_seconds=0)


# --------------------------------------------------- injection end-to-ends


def _device_replay_args(**over):
    train = {
        "mesh": {"dp": 2},
        "turn_based_training": False,
        "observation": False,
        "batch_size": 8,
        "forward_steps": 4,
        "burn_in_steps": 0,
        "device_rollout_games": 8,
        "device_replay": True,
        "device_replay_slots": 64,
        "device_replay_k_steps": 16,
        "minimum_episodes": 20,
        "update_episodes": 30,
        "maximum_episodes": 400,
        "epochs": 3,
        "num_batchers": 1,
        "eval_rate": 0.0,
        "worker": {"num_parallel": 1},
    }
    train.update(over)
    return normalize_args(
        {"env_args": {"env": "ParallelTicTacToe"}, "train_args": train}
    )


@pytest.mark.slow
def test_nan_injection_skips_rolls_back_and_finishes(tmp_path, monkeypatch):
    """The headline e2e: with a NaN poisoning every lr from step 5 on
    (epoch 1 trains clean and lands a verified checkpoint first), the run
    skips every poisoned update, escalates the streak to a verified-
    checkpoint rollback, and still finishes with finite params and the
    sentinel_* counters in metrics.jsonl."""
    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HANDYRL_FAULT_NAN_AT_STEP", "5:1000000")
    # epochs are EPISODE-counted and device generation floods the books,
    # so a slow/loaded host fits only ~1 SGD step per epoch — with 4
    # epochs the run could end at exactly step 5 (the fault onset) with
    # every recorded epoch still clean, flaking the assertions below.
    # 8 epochs guarantees the recorded run crosses the fault window with
    # the SAME assertions (observed marginal on this container 2026-08-04)
    args = _device_replay_args(sentinel_rollback_after=2, epochs=8)
    learner = Learner(args)
    assert learner.run() == 0

    records = read_metrics("metrics.jsonl")
    assert records and records[-1]["steps"] > 5
    last = records[-1]
    # cumulative counters: poisoned steps were skipped, and at least one
    # streak escalated to a rollback onto a verified snapshot
    assert last["sentinel_skipped_steps"] > 0
    assert last["sentinel_rollbacks"] >= 1
    # loss stayed finite through the whole run (the pre-sentinel run ends
    # with loss=nan everywhere)
    for rec in records:
        for v in (rec.get("loss") or {}).values():
            assert np.isfinite(v)
    # ... and so did the params that came out the other end
    for leaf in jax.tree.leaves(learner.trainer.state_host["params"]):
        assert np.isfinite(np.asarray(leaf)).all()
    # the rollback target still exists (GC pinned it)
    assert cp.latest_verified_epoch("models") > 0


@needs4
@pytest.mark.slow
def test_wedged_split_plane_degrades_to_fused_and_finishes(tmp_path, monkeypatch):
    """A rollout thread that wedges after 2 dispatches (simulated stuck
    XLA execute) trips the watchdog; with a zero restart budget the split
    run degrades to fused, keeps generating on the learner mesh, and
    completes its epochs."""
    from handyrl_tpu.runtime.learner import Learner

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HANDYRL_FAULT_WEDGE_ROLLOUT", "2")
    args = _device_replay_args(
        plane="split",
        actor_chips=2,
        param_refresh_updates=2,
        plane_stall_timeout=1.0,
        plane_max_restarts=0,
        epochs=2,
    )
    learner = Learner(args)
    assert learner.run() == 0

    assert os.path.exists("models/latest.ckpt")
    records = read_metrics("metrics.jsonl")
    last = records[-1]
    assert last["steps"] > 0                      # training kept going
    assert last["plane"] == "fused"               # topology flipped loudly
    assert last["plane_watchdog_stalls"] >= 1
    assert last["plane_watchdog_degraded"] == 1
    assert learner.rollout.topology == "fused"
    for v in (last.get("loss") or {}).values():
        assert np.isfinite(v)


@pytest.mark.slow
def test_sigterm_drains_to_verified_checkpoint_and_resumes(tmp_path, monkeypatch):
    """Preemption loop: SIGTERM mid-epoch -> pipelines drain -> final
    manifest-verified checkpoint -> exit resumable (75) -> a relaunch
    with ``restart_epoch: -1`` picks the drain checkpoint up and
    finishes."""
    from handyrl_tpu.runtime.learner import EXIT_RESUMABLE, Learner

    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("HANDYRL_FAULT_SIGTERM_AT_STEP", "6")
    args = _device_replay_args(epochs=50, drain_deadline_seconds=45.0)
    learner = Learner(args)
    code = learner.run()
    assert code == EXIT_RESUMABLE

    drain_epoch = cp.latest_verified_epoch("models")
    assert drain_epoch > 0                        # the drain's final save
    assert cp.verify_snapshot("models", drain_epoch)
    # a truncated metrics tail from the kill window must not break readers
    records = read_metrics("metrics.jsonl") if os.path.exists("metrics.jsonl") else []

    # relaunch the way a supervisor would: auto-resume, run to completion
    # (epochs is an ABSOLUTE target vs model_epoch: one more than the
    # drain checkpoint = one full resumed epoch)
    monkeypatch.delenv("HANDYRL_FAULT_SIGTERM_AT_STEP")
    args2 = _device_replay_args(epochs=drain_epoch + 1, restart_epoch=-1)
    resumed = Learner(args2)
    assert resumed.model_epoch == drain_epoch     # landed on the drain save
    assert resumed.run() == 0
    assert resumed.model_epoch > drain_epoch      # and made progress past it
    final = read_metrics("metrics.jsonl")
    assert len(final) >= len(records)
