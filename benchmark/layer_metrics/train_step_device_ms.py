"""Device milliseconds of one SGD update: the train program's device time
over its executions times the updates one execution fuses."""


def read(run):
    program = run.program("train")
    if program is None or not program["runs"]:
        return None
    fused = run.counters.get("fused_steps", 1)
    return 1e3 * program["seconds"] / (program["runs"] * fused)
