"""Runner ``actor_stream``: a dedicated actor host's loop as the program
runs it (``runtime/actor_host.py`` ``actor_loop``: the streaming device
rollout with the net's hidden per (lane, player), ``device_get`` of each
record batch, ``ship_records``, the version check) on the run's chip,
against the program's own ``PlaneGateway`` on the loopback.  Nothing
publishes: the weights the loop makes from ``--seed`` act for the whole run
(the time between two publishes).

The gateway's ``on_records`` is the runner's clock.  The window opens at the
receipt of the batch after ``warm_dispatches`` with no compile since the one
before, and closes at the last batch received inside ``--seconds`` (a traced
run: ``trace_seconds``, under the profiler); then the loop is told to stop.
``selfplay_steps_per_s`` is the game steps of the batches between the two
(whole dispatches of lanes x k) over the time between their receipts.
``attempted`` counts those dispatches, ``failed`` the ones with a
non-finite ``prob`` or ``value`` or an action that was not legal.

After the window, ``correct``: for each of ``judge_lanes``, the first game
that begins inside the window (a reset: zero state) is followed to its end
or the window's (where a short traced window holds no game's beginning for
one of them, the lane whose game begins earliest stands in).  Each player's observations are rebuilt from the shipped
records (``venv.episode_obs``, the program's own episode assembly), and the
same step-mode apply, at the timed rows (lanes x players) and on the
weights the loop acted on, is run over them with carried state
(``replay``: one ``lax.scan``, commit where observed).  (a) Its ``value``
and chosen-action ``prob`` are held to what the window recorded within
``replay_tolerance``.  (b) Its legal-move logits, values and returns at
every observed step are held to the configuration's plain reference, which
walks the stack a sub-layer at a time in float32 under ``highest``
(``reference.forward_by_layer``), on the three limits of a routed net
(``harness.judge_forward`` says why): forced to the system's choices within
``reference_tolerance``, free within ``choices_agreement_floor``, and the
net's whole-window mode with float32 parameters and compute: its arithmetic
against the reference forced to that pass's choices within
``reference_tolerance_f32``, its choices against the free reference's within
``choices_agreement_floor_f32``.  The replay's and the
float32 window's programs are compiled from shapes in a thread of their own
while the loop sets itself up (the window does not open before that has
ended), so that after the window they are loaded and not compiled: a run has
330 s, and a cold one spends 115 of them compiling the rollout.  A cell file with
``control`` (a dtype's name) rounds the weights through it for the replay
alone (the references read the weights the window acted on, made again from
the seed): the control that has to fail.
"""

from __future__ import annotations

import random
import socket
import threading
import time

from benchmark import harness

STANDARD = ("active", "observing", "legal", "action", "prob", "value", "done", "outcome")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _hold(got, want, tolerance, masks, prefix=""):
    """``harness.compare_outputs`` head by head, each head to its own
    tolerance where the configuration gives a table of them (the policy
    logits are divided by ``logits_divisor``, so their scale and their error
    are a sixteenth of the other heads'): -> (all held, the verdicts,
    name -> [number, limit])."""
    held, verdicts, compared = True, {}, {}
    for head in want:
        limit = float(tolerance[head] if isinstance(tolerance, dict) else tolerance)
        verdict = harness.compare_outputs(
            {head: got[head]}, {head: want[head]}, limit, {head: masks[head]})
        held = held and verdict.pop("ok")
        verdicts.update(verdict)
        compared.update(harness.limits(verdict, limit, prefix))
    return held, verdicts, compared


def run(run: harness.Run) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from handyrl_tpu.config import normalize_args
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.runtime import actor_host
    from handyrl_tpu.runtime.device_rollout import ILLEGAL
    from handyrl_tpu.runtime.plane import PlaneGateway
    from handyrl_tpu.utils import trace as program_trace

    cell, config = run.cell, run.config
    port = _free_port()
    dist = {"role": "actor", "coordinator_address": f"127.0.0.1:{port}", "plane_port": port,
            "initialization_timeout": 60.0}
    cfg = normalize_args({
        "env_args": dict(config["env_args"]),
        "train_args": dict(config.get("train_args", {}), **cell["train_args"],
                           seed=run.seed, distributed=dist),
    })
    try:
        env = make_env(cfg["env_args"])
        module = env.net()
    except TypeError as exc:     # a net argument this checkout's module does not know
        raise harness.NoProgram(f"this checkout's program cannot build the net: {exc}")
    run.require_module(module)
    if not hasattr(actor_host, "actor_loop"):
        raise harness.NoProgram("this checkout's actor host has no loop to hand devices to")
    random.seed(run.seed)
    np.random.seed(run.seed)
    venv = env.vector_env()
    train = cfg["train_args"]
    lanes, k_steps = int(train["device_rollout_games"]), int(train["device_replay_k_steps"])
    players = venv.num_players
    warm = int(cell["warm_dispatches"])
    seconds = min(run.seconds, float(cell["trace_seconds"])) if run.trace else run.seconds

    horizon = int(venv.max_steps)
    judged = [int(lane) for lane in cell["judge_lanes"]]
    rows = len(judged) * players
    env.reset()
    sample = env.observation(env.players()[0])

    def seeded(key):
        """The module's own initialisers, as the loop calls them."""
        return module.init(key, jax.tree.map(lambda x: x[None], sample),
                           module.initial_state((1,)))["params"]

    def replay(p, obs, observing):
        """The step-mode apply at the timed rows over (rows, horizon) of
        observations, commit where observed; the judged rows' heads and choices."""
        hidden = module.initial_state((lanes * players,))

        def body(hidden, step):
            obs_t, seen_t = step
            whole = jax.tree.map(
                lambda x: jnp.zeros((lanes * players,) + x.shape[1:], x.dtype).at[:rows].set(x),
                obs_t)
            keep = jnp.zeros((lanes * players,), bool).at[:rows].set(seen_t > 0)
            out, sown = module.apply({"params": p}, whole, hidden, mutable=["choices"])
            hidden = jax.tree.map(
                lambda h, nh: jnp.where(keep.reshape((-1,) + (1,) * (h.ndim - 1)), nh, h),
                hidden, out["hidden"])
            heads = {k: out[k][:rows].astype(jnp.float32) for k in ("policy", "value", "return")}
            chosen = {name: layer["mixer"]["chosen"][0][:rows]
                      for name, layer in sown.get("choices", {}).items()}
            return hidden, (heads, chosen)

        by_step = (jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), obs), jnp.moveaxis(observing, 1, 0))
        _, (heads, chosen) = jax.lax.scan(body, hidden, by_step)
        return jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), (heads, chosen))

    def window_f32(p, obs, observing):
        """The net's other mode, float32 parameters and compute."""
        out = module.apply({"params": jax.tree.map(lambda x: x.astype(jnp.float32), p)},
                           obs, None, seq=True, key_mask=observing)
        return {k: out[k] for k in ("policy", "value", "return")}, out["choices"]

    precompiled, precompile_error = threading.Event(), []

    def precompile():
        """The judge's two programs compiled from shapes while the loop makes
        its weights and compiles its own: set-up, beside set-up.  The
        executables are let go at once (they would hold device memory through
        the window); after the window ``jax.jit`` finds them in the compile
        cache.  The window does not open before this has ended."""
        try:
            from jax.sharding import NamedSharding, PartitionSpec

            from handyrl_tpu.parallel import make_mesh

            held = NamedSharding(make_mesh({"dp": -1}, list(run.devices)), PartitionSpec())
            weights = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=held),
                jax.eval_shape(seeded, jax.random.PRNGKey(0)))
            obs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((rows, horizon) + np.shape(x), np.float32), sample)
            seen = jax.ShapeDtypeStruct((rows, horizon), np.float32)
            jax.jit(replay).lower(weights, obs, seen).compile()
            with jax.default_matmul_precision("highest"):
                jax.jit(window_f32).lower(weights, obs, seen).compile()
        except BaseException as exc:      # the judge then compiles them itself
            precompile_error.append(repr(exc))
        finally:
            precompiled.set()

    batches, stop, clock_error = [], threading.Event(), []
    window = {}     # "open", "close": indices into ``batches``

    def flat(a, b):
        return (a["hits"] + a["misses"] == b["hits"] + b["misses"]
                and b["compile_s"] - a["compile_s"] < 0.05)

    def on_records(records):
        try:
            batches.append({"t": time.monotonic(), "compile": run.compile.snapshot(),
                            "records": records})
            at = len(batches) - 1
            if "open" not in window:
                if (at >= warm and precompiled.is_set()
                        and flat(batches[at - 1]["compile"], batches[at]["compile"])):
                    window["open"] = at
                    if run.trace:
                        harness.start_profile(run)
            elif "close" not in window and batches[at]["t"] - batches[window["open"]]["t"] >= seconds:
                # the batch before this one is the last inside the window; the
                # run is concluding: a clean stop, not a lost actor host
                stop.set()
                gateway.begin_stop()
                window["close"] = max(at - 1, window["open"] + 1)
                first, last = batches[window["open"]], batches[window["close"]]
                if run.trace:
                    harness.stop_profile(run)
                run.t_window = first["t"]
                run.setup_compile = first["compile"]
                run.values["setup_s"] = first["t"] - run.t_process
                run.close_window(last["t"] - first["t"], end_compile=last["compile"])
        except BaseException as exc:      # the run must still end
            clock_error.append(repr(exc))
            stop.set()

    gateway = PlaneGateway(dist, on_records=on_records)
    gateway.start()
    threading.Thread(target=precompile, name="bench-precompile", daemon=True).start()
    if run.trace:
        program_trace.configure({"enabled": True, "path": "trace.jsonl"})
    try:
        done = actor_host.actor_loop(cfg, run.devices, stop)
    finally:
        gateway.stop()
        program_trace.shutdown()
    run.checks["actor_loop_ended"] = not clock_error and "close" in window
    if clock_error:
        run.notes["clock_error"] = clock_error
    if "close" not in window:
        raise RuntimeError(
            f"the window never closed: {len(batches)} record batches, opened at "
            f"{window.get('open')} (needs {warm} warm dispatches and no compile between two)")

    opened, closed = window["open"], window["close"]
    inside = batches[opened + 1:closed + 1]
    dispatches = len(inside)
    game_steps = dispatches * lanes * k_steps
    run.values["selfplay_steps_per_s"] = game_steps / run.window_s
    bad = 0
    for batch in inside:
        rec = batch["records"]
        active = np.asarray(rec["active"], bool)
        chosen_legal = np.take_along_axis(
            np.asarray(rec["legal"], bool), np.asarray(rec["action"])[..., None], axis=-1)[..., 0]
        sound = (np.isfinite(rec["prob"]).all() and np.isfinite(rec["value"]).all()
                 and chosen_legal[active].all()
                 and np.asarray(rec["prob"]).shape[:2] == (k_steps, lanes))
        bad += int(not sound)
    run.attempted, run.failed = dispatches, bad
    gaps = np.diff([b["t"] for b in batches[opened:closed + 1]]) * 1e3
    run.counters.update(
        dispatches=dispatches, game_steps=game_steps, window_s=run.window_s,
        lanes=lanes, k_steps=k_steps, rows_per_step=lanes * players,
        dispatches_before_window=opened + 1, dispatches_in_all=done["dispatches"])
    run.notes["dispatch_interval_ms"] = {
        "median": float(np.median(gaps)), "max": float(gaps.max()), "min": float(gaps.min())}
    run.notes["params"] = int(sum(x.size for x in jax.tree.leaves(done["params"])))
    run.notes["param_dtypes"] = sorted({x.dtype.name for x in jax.tree.leaves(done["params"])})
    if run.trace:
        lo, hi = batches[opened]["t"], batches[closed]["t"]
        run.spans = [s for s in program_trace.read_trace("trace.jsonl")
                     if lo <= s.get("t_mono", -1.0) <= hi]
        # the loop's thread by span, as shares of the window
        run.notes["actor_thread"] = {
            name: sum(s["dur_s"] for s in run.spans if s["name"] == name) / max(hi - lo, 1e-9)
            for name in ("actor.dispatch", "actor.fetch", "actor.ship", "actor.poll")}
        # what the step mode counted, a mean over the window's dispatches
        counted = [s["attrs"] for s in run.spans if s["name"] == "actor.counters"]
        for name in (counted[0] if counted else ()):
            run.counters["counter_" + name] = float(np.mean([c[name] for c in counted]))

    # -- the timed program's output against itself and the plane reference ----
    steps = {name: np.concatenate([np.asarray(b["records"][name]) for b in inside])
             for name in inside[0]["records"]}          # (dispatches x k, lanes, ...)
    del batches, inside
    def first_game(lane):
        """(begin, end) of the lane's first game that begins inside the window."""
        ends = np.flatnonzero(steps["done"][:, lane])
        if not len(ends) or ends[0] + 1 >= len(steps["done"]):
            return None
        begin = ends[0] + 1
        return begin, min(ends[1] + 1 if len(ends) > 1 else len(steps["done"]), begin + horizon)

    # the cell's lanes; where a short (traced) window holds no game's beginning
    # for one of them, the lane whose game begins earliest stands in
    games = {lane: span for lane in range(lanes) if (span := first_game(lane))}
    spare = sorted((lane for lane in games if lane not in judged), key=lambda lane: games[lane][0])
    replayed = [lane if lane in games else (spare.pop(0) if spare else None) for lane in judged]
    if not any(lane is not None for lane in replayed):
        raise RuntimeError("no game of any lane begins inside the window")
    obs_seq, seen, legal, taken, rec_prob, rec_value, lengths = None, [], [], [], [], [], []
    for lane in replayed:
        # a lane with no such game is rows that observe nothing
        begin, end = games[lane] if lane is not None else (0, 0)
        lengths.append(end - begin)
        pad = horizon - (end - begin)
        take = lambda name: steps[name][begin:end, lane or 0]  # noqa: E731
        observing = take("observing").astype(np.float32)            # (T, P)
        compact = {name: take(name) for name in steps if name not in STANDARD}
        obs = venv.episode_obs(compact, observing)                   # leaves (T, P, ...)
        widen = lambda x: np.moveaxis(  # noqa: E731
            np.pad(np.asarray(x), ((0, pad),) + ((0, 0),) * (np.ndim(x) - 1)), 0, 1)
        obs = jax.tree.map(lambda x: widen(x).astype(np.float32), obs)   # leaves (P, horizon, ...)
        obs_seq = obs if obs_seq is None else jax.tree.map(
            lambda a, b: np.concatenate([a, b]), obs_seq, obs)
        seen.append(widen(observing))
        legal.append(widen(take("legal") & (take("active")[..., None] > 0)))
        taken.append(widen(take("action")))
        rec_prob.append(widen(take("prob")))
        rec_value.append(widen(take("value")))
    seen, legal, taken = (np.concatenate(x) for x in (seen, legal, taken))       # (rows, horizon, .)
    rec_prob, rec_value = np.concatenate(rec_prob), np.concatenate(rec_value)
    run.notes["judged_games"] = {"lanes": replayed, "steps": [int(n) for n in lengths],
                                 "observed_steps": int(seen.sum())}
    del steps

    params = done["params"]
    done.clear()
    control = cell.get("control")
    if control:
        # the control that has to fail: the replay on weights rounded through a
        # narrower type.  Leaf by leaf and each cast a program of its own: two
        # trees do not fit the chip, and inside one program XLA drops a
        # round trip through fewer bits as excess precision
        def rounded(x):
            y = x.astype(jnp.dtype(control)).astype(x.dtype)
            x.delete()
            return y

        params = jax.tree.map(rounded, params)
        run.notes["control"] = control

    before = run.compile.snapshot()
    got, chosen = jax.device_get(jax.jit(replay)(params, obs_seq, seen))
    if control:     # the references read the weights the window acted on, made again from the seed
        for leaf in jax.tree.leaves(params):
            leaf.delete()
        params = jax.jit(seeded)(jax.random.PRNGKey(run.seed))
    # the rollout's and the replay's executables go, and what the device holds
    # for them: the float32 comparisons below need the room beside the weights
    jax.clear_caches()
    chosen = {name: np.where(seen[..., None] > 0, c, 0) for name, c in chosen.items()}
    acting = legal.any(axis=-1)
    masked = np.where(legal, got["policy"], got["policy"] - ILLEGAL)
    masked = masked - masked.max(axis=-1, keepdims=True)
    prob = np.exp(masked) / np.exp(masked).sum(axis=-1, keepdims=True)
    prob = np.take_along_axis(prob, taken[..., None], axis=-1)[..., 0]
    tolerance = float(config["replay_tolerance"])
    again = {"replay_prob": float(np.abs(prob - rec_prob)[acting].max()),
             "replay_value": float(np.abs(got["value"][..., 0] - rec_value)[seen > 0].max())}
    run.compared.update({name: [diff, tolerance] for name, diff in again.items()})
    run.checks["replay_matches_window"] = all(diff <= tolerance for diff in again.values())

    reference = run.reference()
    masks = {"policy": legal, "value": seen[..., None] > 0, "return": seen[..., None] > 0}

    def plain(**given):
        with jax.default_matmul_precision("highest"):
            return dict(jax.device_get(reference.forward_by_layer(
                params, obs_seq, seen, config, **given)))

    forced, free = plain(choices=chosen), plain()
    if harness.choices_agreement(forced.pop(harness.CHOICES), chosen) != 1.0:
        raise ValueError(f"{config['name']}: the reference did not use the choices it was given")
    own = free.pop(harness.CHOICES)
    held, verdict, compared = _hold(got, forced, config["reference_tolerance"], masks)
    floor = float(config["choices_agreement_floor"])
    agreement = harness.choices_agreement(chosen, own, seen > 0)
    run.compared.update(compared, choices_agreement=[agreement, floor])
    run.checks.update(matches_reference=held, choices_agree=agreement >= floor)
    run.notes.update(
        reference_max_abs_diff=verdict, choices_agreement=agreement,
        reference_free_max_abs_diff=_hold(got, free, config["reference_tolerance"], masks)[1])

    # the net's other mode, float32 parameters and compute, against the free reference
    # (a discrete choice cannot be held to a tolerance in float32 either: of a few
    # thousand top-k sets one may turn on a near-tie between two float32 programs,
    # and reads as a wrong expert.  So the arithmetic is held to the reference
    # forced to this pass's own choices, and the choices to the free reference's)
    with jax.default_matmul_precision("highest"):
        exact, exact_chosen = jax.device_get(jax.jit(window_f32)(params, obs_seq, seen))
    exact_chosen = {name: np.where(seen[..., None] > 0, c, 0) for name, c in exact_chosen.items()}
    forced = plain(choices=exact_chosen)
    forced.pop(harness.CHOICES)
    held, verdict, compared = _hold(exact, forced, config["reference_tolerance_f32"], masks, "f32_")
    floor = float(config["choices_agreement_floor_f32"])
    agreement = harness.choices_agreement(exact_chosen, own, seen > 0)
    run.compared.update(compared, f32_choices_agreement=[agreement, floor])
    run.checks["matches_reference_f32"] = held and agreement >= floor
    run.notes.update(
        reference_f32_max_abs_diff=verdict, f32_choices_agreement=agreement,
        reference_f32_free_max_abs_diff=_hold(
            exact, free, config["reference_tolerance_f32"], masks)[1])
    after = run.compile.snapshot()
    run.notes["judge_compile"] = {
        "hits": after["hits"] - before["hits"], "misses": after["misses"] - before["misses"],
        "compile_s": after["compile_s"] - before["compile_s"],
        "precompile_error": precompile_error or None}
