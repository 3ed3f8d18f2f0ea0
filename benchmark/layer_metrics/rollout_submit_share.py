"""Share of the window the rollout thread spent under ``rollout.submit``:
handing an ingest's counts to the server loop, which serves no request while
it runs an epoch boundary.  From the program's own spans in ``trace.jsonl``
on the thread named ``device-rollout-*``, as ``rollout_wait_share`` reads
its waits (that reader keeps this share in its notes)."""

from benchmark import trace_reduce

THREAD = "device-rollout-"
SPAN = "rollout.submit"


def read(run):
    # a number of the traced window: a run whose profile was never handed
    # back (``profile_collected`` fails, the run is not ``correct``) has no
    # such window to report on (tests/test_profile_wait.py lists what answers
    # then, by name)
    if not run.xplane or not run.spans or not run.window_s or run.t_window is None:
        return None
    spans = [(s["t_mono"], s["t_mono"] + s["dur_s"]) for s in run.spans
             if s["name"] == SPAN and s.get("thread", "").startswith(THREAD)]
    if not spans:
        return None     # a program without the span: nothing to read
    lo, hi = run.t_window, run.t_window + run.window_s
    covered = trace_reduce.clip(trace_reduce.merge(spans), lo, hi)
    return 100.0 * trace_reduce.measure(covered) / run.window_s
