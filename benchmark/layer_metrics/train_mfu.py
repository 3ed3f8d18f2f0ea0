"""Model FLOP/s utilization of training: the operations one update needs
(forward and backward, from shapes: benchmark/flops/<family>.py; padded,
masked-out and recomputed work does not count) times updates a second on
the host's clock, over chips times the peak bf16 rate."""


def read(run):
    updates, window = run.counters.get("updates"), run.counters.get("window_s")
    if not updates or not window:
        return None
    work = run.required_work()
    peak = run.peaks()["bf16_flops_per_s"] * len(run.devices)
    return 100.0 * work["flops"] * (updates / window) / peak
