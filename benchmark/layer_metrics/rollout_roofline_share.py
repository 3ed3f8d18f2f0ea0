"""The least time the chip could take for one step of the rollout (the
larger of its required operations over the peak rate and its required bytes
over the peak bandwidth: held weights once, the observing rows' state read
and written once) over the device time a step takes: the rollout program's
whole runs over their number and the steps of a dispatch.  Which of the two
bounds it, and both in milliseconds, go to the run's notes, and with them
the share of the traced window in which no operation ran on the chip."""

from benchmark import harness


def read(run):
    shared = harness.load_module(run.path("layer_metrics", "actor_step.py"))
    program, work, steps = shared.program(run), shared.work(run), run.counters.get("k_steps")
    if program is None or not program["whole_runs"] or not work or not steps:
        return None
    step_s = program["whole_seconds"] / program["whole_runs"] / steps
    peaks, chips = run.peaks(), len(run.devices)
    compute_s = work["flops"] / chips / peaks["bf16_flops_per_s"]
    memory_s = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    run.notes["rollout_roofline"] = {
        "bound": "compute" if compute_s >= memory_s else "memory",
        "least_ms": {"compute": compute_s * 1e3, "memory": memory_s * 1e3},
        "step_ms": step_s * 1e3,
        # the cell trains nothing, so it lists no ``device_idle_share``: here instead
        "device_idle_share": 100.0 * run.reduced["idle_s"] / run.reduced["window_s"]}
    return 100.0 * max(compute_s, memory_s) / step_s
