"""Runner ``device_loop``: the self-play training loop as users run it:
``Learner`` with the rollout, the replay rings, the trainer thread, device
eval, checkpoints and epoch boundaries, all on the chip.

The learner has no stop but ``epochs``, and an epoch count cannot hit a
time budget, so a watcher thread ends the run by setting the
``shutdown_flag`` the learner sets itself after its last epoch.  The window
is aligned to epoch records (so it holds whole rollout dispatches): it opens
at the first record after warm-up (``warm_records`` written, no compile
between the last two, and the rings booked full: as many game steps as
lanes x slots, so the memory the cell reports holds replay and the sampler
and the ingest work on a ring as a long run has it) and closes at the last
record before ``--seconds`` have passed.  The cell's ``minimum_episodes``
makes the fill quick: until then the trainer waits and the rollout has the
chip alone.  Rates are counter differences between those two records over
the difference of their ``t_mono``: the program's own per-epoch wall rates
are not used.

A traced run profiles from the opening record to the first record that is
``trace_seconds`` later or, where the cell's file gives ``trace_updates``,
that many SGD updates later, whichever comes first, and at least
``MIN_TRACE_EPOCHS`` records later: ``ProfilerSession.stop()`` and the
reducer cost seconds for every MB, a profile grows with the updates it
holds, and a faster program must not outgrow the run's limit.  Then it stops.

Outside the window: the net the loop runs (same module, the jitted apply
the rollout and the engines use) against the configuration's plain
reference on seeded observations, with seeded weights whose heads are not
zero.
"""

from __future__ import annotations

import json
import math
import os
import random
import threading
import time

from benchmark import harness, traffic

POLL_S = 0.02
# epoch boundaries the traced window holds at least: epoch_stall_share and
# the idle gaps are read there
MIN_TRACE_EPOCHS = 2


def _read_new_records(path, offset):
    """Complete lines appended to metrics.jsonl since ``offset``."""
    if not os.path.exists(path):
        return [], offset
    with open(path, "rb") as f:
        f.seek(offset)
        chunk = f.read()
    end = chunk.rfind(b"\n") + 1
    lines = chunk[:end].decode().splitlines()
    return [json.loads(line) for line in lines if line.strip()], offset + end


def run(run: harness.Run) -> None:
    import jax
    import numpy as np

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel
    from handyrl_tpu.runtime.learner import Learner
    from handyrl_tpu.utils import trace as program_trace

    cell, config = run.cell, run.config
    train_args = dict(
        config.get("train_args", {}), **cell["train_args"], seed=run.seed,
        metrics_path="metrics.jsonl", model_dir="models", epochs=-1,
    )
    if run.trace:
        train_args["trace"] = {"enabled": True, "path": "trace.jsonl",
                               "ring_size": 65536}
    cfg = normalize_args({"env_args": dict(config["env_args"]), "train_args": train_args})
    run.require_module(make_env(cfg["env_args"]).net())
    random.seed(run.seed)
    np.random.seed(run.seed)
    learner = Learner(cfg)

    seconds = run.seconds
    trace_seconds = float(cell["trace_seconds"])
    trace_updates = float(cell.get("trace_updates", math.inf))
    warm_records = int(cell["warm_records"])
    train = cfg["train_args"]
    ring_steps = int(train["device_rollout_games"]) * int(train["device_replay_slots"])
    records, watcher_error = [], []

    def watch():
        try:
            offset, opened, opened_at, profiling = 0, None, 0, False
            flat = lambda a, b: (  # noqa: E731
                a["hits"] + a["misses"] == b["hits"] + b["misses"]
                and b["compile_s"] - a["compile_s"] < 0.05)
            while True:
                time.sleep(POLL_S)
                new, offset = _read_new_records("metrics.jsonl", offset)
                for record in new:
                    record["_compile"] = run.compile.snapshot()
                    # game steps the rings have booked so far: an epoch's
                    # mean episode length is its booked steps over its episodes
                    before = records[-1] if records else {"episodes": 0, "_booked": 0.0}
                    record["_booked"] = before["_booked"] + (
                        record.get("device_mean_episode_len", 0.0)
                        * (record["episodes"] - before["episodes"]))
                    records.append(record)
                    if (opened is None and len(records) >= warm_records
                            and record["_booked"] >= ring_steps
                            and flat(records[-2]["_compile"], record["_compile"])):
                        opened, opened_at = record, len(records)
                        record["_opens_window"] = True
                        if run.trace:
                            harness.start_profile(run)
                            profiling = True
                    elif (profiling and len(records) - opened_at >= MIN_TRACE_EPOCHS
                          and (record["t_mono"] - opened["t_mono"] >= trace_seconds
                               or record["steps"] - opened["steps"] >= trace_updates)):
                        record["_closes_trace"] = True
                        learner.shutdown_flag = True    # before the slow part
                        harness.stop_profile(run)
                        return
                if opened is not None:
                    elapsed = time.monotonic() - opened["t_mono"]
                    limit = 2.5 * trace_seconds if run.trace else seconds
                    if elapsed >= limit:
                        learner.shutdown_flag = True
                        if profiling:
                            harness.stop_profile(run)
                        return
                if learner.shutdown_flag:
                    return
        except BaseException as exc:      # the run must still end
            watcher_error.append(repr(exc))
            learner.shutdown_flag = True

    watcher = threading.Thread(target=watch, name="bench-watcher", daemon=True)
    watcher.start()
    code = learner.run()
    harness.join_profiler(run, watcher)
    program_trace.shutdown()
    run.checks["learner_exit_0"] = code == 0 and not watcher_error
    if watcher_error:
        run.notes["watcher_error"] = watcher_error

    start = next((i for i, r in enumerate(records) if r.get("_opens_window")), None)
    if start is None:
        raise RuntimeError(
            f"the window never opened: {len(records)} epoch records, need "
            f"{warm_records}, no compile between the last two and {ring_steps} "
            f"game steps booked (had {records[-1]['_booked'] if records else 0:.0f})")
    if run.trace:
        stop = next((i for i, r in enumerate(records) if r.get("_closes_trace")),
                    len(records) - 1)
    else:
        deadline = records[start]["t_mono"] + seconds
        stop = max(i for i, r in enumerate(records) if r["t_mono"] <= deadline)
    first, last = records[start], records[stop]
    inside = records[start + 1:stop + 1]
    if not inside:
        raise RuntimeError("no whole epoch fits the window: raise --seconds")
    run.t_window = first["t_mono"]
    run.setup_compile = first["_compile"]
    run.values["setup_s"] = first["t_mono"] - run.t_process
    window_s = last["t_mono"] - first["t_mono"]
    run.close_window(window_s, end_compile=last["_compile"])

    updates = last["steps"] - first["steps"]
    game_steps = last["_booked"] - first["_booked"]
    per_dispatch = int(train["device_rollout_games"]) * int(train["device_replay_k_steps"])
    dispatches = int(round(game_steps / per_dispatch))
    run.values["trained_steps_per_s"] = (
        updates * int(train["batch_size"]) * int(train["forward_steps"]) / window_s)
    run.values["selfplay_steps_per_s"] = game_steps / window_s
    bad_epochs = [
        r for r in inside
        if not all(math.isfinite(v) for v in (r.get("loss") or {"": math.nan}).values())
    ]
    delta = lambda key: last.get(key, 0) - first.get(key, 0)  # noqa: E731
    run.attempted = int(updates + dispatches)
    run.failed = int(delta("sentinel_spike_steps") + delta("sentinel_skipped_steps")
                     + len(bad_epochs))
    run.checks["losses_finite"] = not bad_epochs
    run.checks["no_fallback_counter"] = all(
        r.get(key, 0) == 0 for r in records for key in harness.FALLBACK_COUNTERS)
    run.checks["device_eval_every_epoch"] = all(r.get("win_rate") for r in inside)
    run.counters.update(
        updates=updates, episodes=last["episodes"] - first["episodes"],
        game_steps=game_steps, rollout_dispatches=dispatches, epochs=len(inside),
        epoch_s=window_s / len(inside), window_s=window_s,
        fused_steps=int(train["fused_steps"]),
        ring_steps=ring_steps, ring_turns_before_window=first["_booked"] / ring_steps,
    )
    run.notes.update(
        records=len(records), loss_last=last.get("loss"),
        win_rate_last=last.get("win_rate"),
        program_mfu_last=last.get("mfu"),
        mean_episode_len=last.get("device_mean_episode_len"),
    )
    if run.trace and os.path.exists("trace.jsonl"):
        lo, hi = first["t_mono"], last["t_mono"]
        run.spans = [s for s in program_trace.read_trace("trace.jsonl")
                     if lo <= s.get("t_mono", -1.0) <= hi]

    # -- the net against the plain reference, outside the window ----------
    env = make_env(cfg["env_args"])
    module = learner.module
    params = traffic.seeded_params(module, env, run.seed)
    obs = traffic.observation_pool(env, int(cell["check_samples"]))
    system = InferenceModel(module, {"params": params}).inference_batch(obs)
    reference = run.reference()
    with jax.default_matmul_precision("highest"):
        want = jax.device_get(jax.jit(reference.forward)(params, obs))
    verdict = harness.compare_outputs(
        {k: system[k] for k in want}, want, float(config["reference_tolerance"]))
    run.compared.update(harness.limits(verdict, float(config["reference_tolerance"])))
    run.checks["matches_reference"] = verdict.pop("ok")
    run.notes["reference_max_abs_diff"] = verdict
