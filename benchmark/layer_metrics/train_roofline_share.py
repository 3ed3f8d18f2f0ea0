"""The least time the chip could take for one update (the larger of
required operations over peak FLOP/s and required bytes over peak bytes/s,
per chip) over the device time one update takes.  Which of the two bounds
it is written to the run's notes."""

from benchmark import harness


def read(run):
    reader = harness.load_module(run.path("layer_metrics", "train_step_device_ms.py"))
    step_ms = reader.read(run)
    if not step_ms:
        return None
    work, peaks, chips = run.required_work(), run.peaks(), len(run.devices)
    compute_s = work["flops"] / chips / peaks["bf16_flops_per_s"]
    memory_s = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    run.notes["roofline_bound"] = "compute" if compute_s >= memory_s else "memory"
    run.notes["roofline_least_ms"] = {"compute": compute_s * 1e3, "memory": memory_s * 1e3}
    return 100.0 * max(compute_s, memory_s) * 1e3 / step_ms
