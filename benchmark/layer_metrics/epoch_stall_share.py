"""Share of the traced window in which the chip was idle while one of the
learner's boundary spans was open (the cell's ``stall_spans``: snapshot
wait, metrics fetch, checkpoint save, device eval), on any thread: the
trace's idle gaps intersected with the union of those host spans."""

from benchmark import trace_reduce


def read(run):
    names = run.cell.get("stall_spans")
    if run.reduced is None or not names:
        return None
    spans = [(s, e) for name, s, e, _ in run.reduced["host"] if name in names]
    if not spans:
        return None
    stalled = trace_reduce.intersect(trace_reduce.merge(spans), run.reduced["gaps"])
    return 100.0 * trace_reduce.measure(stalled) / run.reduced["window_s"]
