"""Runner ``train_step``: SGD updates through ``TrainContext`` on staged
device batches, and nothing else of the program.

Set-up: weights from ``--seed`` made on the device in one jitted call;
``n_batches`` host batches of seeded random-play windows (the program's
own Generator -> EpisodeStore -> make_batch path), put on the device once;
a routed net's selection biases balanced on them
(``traffic.balance_routers``: rounds and the worst held expert's load over
its share go to ``notes.router_balance``); two warm-up updates, the first
of which compiles or loads the one program.
Window: updates back to back, rotating the staged batches, at most
``in_flight`` dispatched ahead of the host; the clock stops after the last
update's ``block_until_ready``.  A traced run measures ``trace_seconds``
under the profiler instead of ``--seconds``.

After the window, outside it: every update's loss is fetched and checked
(with them the step's ``counter_*`` metrics, whose mean per update goes to
``run.counters`` and whose first and last update's to
``notes.counters_first_last``), and the forward pass the train step runs
(``forward_prediction``, in the cell's compute dtype) is compared with the
configuration's plain float32 reference on one batch row
(``harness.judge_forward``; a routed net hands its choices to the reference),
on the seeded weights with the biases the window was timed on
(``judged_biases_are_the_timed``: the state's, read before the first update).
"""

from __future__ import annotations

import random
import time

from benchmark import harness, traffic


def run(run: harness.Run) -> None:
    import jax
    import numpy as np

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.parallel import TrainContext, make_mesh
    from handyrl_tpu.parallel.train_step import forward_prediction
    from handyrl_tpu.utils import trace as program_trace

    cell, config = run.cell, run.config
    cfg = normalize_args({
        "env_args": dict(config["env_args"]),
        "train_args": dict(config.get("train_args", {}), **cell["train_args"],
                           seed=run.seed),
    })
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(run.seed)
    np.random.seed(run.seed)
    env = make_env(args["env"])
    module = env.net()
    run.require_module(module)

    params = traffic.seeded_params(module, env, run.seed)
    host_batches = traffic.random_play_batches(
        env, module, args, int(cell["n_batches"]), int(cell["fill_episodes"]))
    ctx = TrainContext(module, args, make_mesh(cell["mesh"], devices=run.devices))
    batches = [ctx.put_batch(b) for b in host_batches]
    # a routed net's selection biases, balanced on the staged batches: what
    # the whole window chooses by (no gradient reaches them)
    params, biases, run.notes["router_balance"] = traffic.balanced_params(
        module, params, ctx.args, batches)
    state = ctx.init_state(params)
    del params
    timed_biases = traffic.router_biases(state["params"])
    lr = float(cell["lr"])
    for i in range(2):          # the first compiles or loads; the second proves it
        state, metrics = ctx.train_step(state, batches[i % len(batches)], lr)
    jax.block_until_ready((state, metrics))

    seconds = min(run.seconds, float(cell["trace_seconds"])) if run.trace else run.seconds
    if run.trace:
        program_trace.configure({"enabled": True, "path": "trace.jsonl"})
        harness.start_profile(run)
    pending, fetched, stamps = [], [], []
    in_flight = int(cell["in_flight"])
    run.open_window()
    t0 = time.perf_counter()
    updates = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.train_step"):
            state, metrics = ctx.train_step(state, batches[updates % len(batches)], lr)
        updates += 1
        pending.append(metrics)
        if len(pending) > in_flight:
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(pending[0])
            fetched.append(pending.pop(0))
            stamps.append(time.perf_counter())
            if stamps[-1] - t0 >= seconds:
                break
    with jax.profiler.TraceAnnotation("bench.block"):
        jax.block_until_ready((state, pending))
    window_s = time.perf_counter() - t0
    if run.trace:
        harness.stop_profile(run)
        program_trace.shutdown()
        run.spans = program_trace.read_trace("trace.jsonl")
    run.close_window(window_s)

    fetched = jax.device_get(fetched + pending)
    losses = np.asarray([float(m["total"]) / max(float(m["dcnt"]), 1.0) for m in fetched])
    skipped = sum(float(m.get("sentinel_bad", 0.0)) for m in fetched)
    run.attempted = updates
    run.failed = int((~np.isfinite(losses)).sum() + skipped)
    steps = updates * int(args["batch_size"]) * int(args["forward_steps"])
    run.values["trained_steps_per_s"] = steps / window_s
    run.counters.update(updates=updates, updates_per_s=updates / window_s,
                        window_s=window_s)
    # what the step counts on the device (rows routed, a buffer's bound): mean per
    # update, and the window's first and last update's (a router that drifts shows)
    for key in fetched[0]:
        if key.startswith("counter_"):
            run.counters[key] = float(np.mean([float(m[key]) for m in fetched]))
            run.notes.setdefault("counters_first_last", {})[key] = [
                float(fetched[0][key]), float(fetched[-1][key])]
    # when each update was seen to end: a run that reads far off says whether
    # every update was slower or a few stalled (2 of 22 read 4% and 9% low in
    # PR 33 and left nothing to tell by)
    gaps = np.diff(stamps) * 1e3
    if len(gaps):
        typical = float(np.median(gaps))
        run.notes["update_interval_ms"] = {
            "median": typical, "max": float(gaps.max()),
            "over_1.05_median": int((gaps > 1.05 * typical).sum()),
            "excess_s": float(np.maximum(gaps - typical, 0.0).sum() / 1e3),
        }
    run.notes.update(loss_first=float(losses[0]), loss_last=float(losses[-1]),
                     params=int(sum(x.size for x in jax.tree.leaves(state["params"]))))

    # -- the train step's forward against the plain reference, one row ----
    row = jax.tree.map(lambda x: np.asarray(x)[:1], host_batches[0])

    def system_forward(p, batch, dtype=args.get("compute_dtype")):
        return forward_prediction(module, traffic.in_compute_dtype(p, dtype), batch,
                                  dict(ctx.args, compute_dtype=dtype))

    # on the seeded weights, drawn a second time (the state's are the window's
    # updates old: at lr 1e-5 they saturated the value head within tens of
    # them), under the biases set-up balanced and the window was timed on
    del state
    params = traffic.with_router_biases(
        traffic.seeded_params(module, env, run.seed), biases)
    run.checks["judged_biases_are_the_timed"] = traffic.same_biases(
        timed_biases, traffic.router_biases(params))
    burn_in = int(args["burn_in_steps"])
    legal = (row["action_mask"][:, burn_in:] == 0) & (row["turn_mask"][:, burn_in:] > 0)
    observed = row["observation_mask"][:, burn_in:] > 0
    checks, notes, compared = harness.judge_forward(
        system_forward, run.reference().forward_rows, params, row, config, burn_in,
        mask_of=lambda head: legal if head == "policy" else observed,
        system_f32=lambda p, batch: system_forward(p, batch, "float32"))
    run.checks.update(checks)
    run.checks["losses_finite"] = bool(np.isfinite(losses).all())
    run.notes.update(notes)
    run.compared.update(compared)
