"""Share of the window the rollout thread spent waiting for the chip: under
``replay.stats_fetch`` (blocked until the ingest before last has executed,
so behind whatever the device has queued) or ``dispatch.wait`` (the
dispatch lock), from the program's own spans in ``trace.jsonl`` on the
thread named ``device-rollout-*``.

Beside it, in the run's notes: the share under ``rollout.submit`` (handing
the ingest's counts to the server loop, which serves no request while it
runs an epoch boundary), under ``rollout.budget_wait`` (the deliberate
yield once the epoch's episodes are in) and under each of the thread's
other spans, and the mean period of ``rollout.dispatch``."""

from benchmark import trace_reduce

THREAD = "device-rollout-"
WAITS = ("replay.stats_fetch", "dispatch.wait")
NOTED = ("rollout.submit", "rollout.budget_wait", "rollout.dispatch", "rollout.ingest",
         "dispatch.run")


def read(run):
    if not run.spans or not run.window_s or run.t_window is None:
        return None
    mine = [s for s in run.spans if s.get("thread", "").startswith(THREAD)]
    if not any(s["name"] == "replay.stats_fetch" for s in mine):
        return None     # a program without the span: nothing to read
    lo, hi = run.t_window, run.t_window + run.window_s

    def share(names):
        spans = [(s["t_mono"], s["t_mono"] + s["dur_s"]) for s in mine if s["name"] in names]
        covered = trace_reduce.clip(trace_reduce.merge(spans), lo, hi)
        return 100.0 * trace_reduce.measure(covered) / run.window_s

    starts = sorted(s["t_mono"] for s in mine if s["name"] == "rollout.dispatch")
    run.notes["rollout_thread"] = dict(
        {name + "_share": share((name,)) for name in WAITS + NOTED},
        dispatches=len(starts),
        dispatch_period_ms=(
            1e3 * (starts[-1] - starts[0]) / (len(starts) - 1) if len(starts) > 1 else None),
    )
    return share(WAITS)
