"""Share of the traced window in which a collective ran on a chip while no
other operation did (averaged over chips): what the all-reduce costs when
nothing hides it."""


def read(run):
    if run.reduced is None or len(run.devices) < 2:
        return None
    run.notes["collective_s"] = run.reduced["collective_s"]
    return 100.0 * run.reduced["collective_exposed_s"] / run.reduced["window_s"]
