"""Share of the traced window the chip spent in the replay rings' ingest
program (the XLA module the cell's file names for ``ingest``): folding one
rollout dispatch's records into the rings."""


def read(run):
    program = run.program("ingest")
    if program is None:
        return None
    return 100.0 * program["seconds"] / run.reduced["window_s"]
