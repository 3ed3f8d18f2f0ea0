"""What the readers of named scopes (``layer_metrics/*_roofline.py``,
``*_step_share.py``) share: a scope's device time as a share of
the train program's, and as a share of its roofline."""


def step_share(run, scope):
    """Percent of the train program's device time spent under ``scope``."""
    inside, program = run.scope(scope), run.program("train")
    if inside is None or program is None or not program["seconds"]:
        return None
    return 100.0 * inside["seconds"] / program["seconds"]


def roofline(run, scope, rows_counter=None):
    """The least time the scope's required work could take (the larger of
    its operations over the peak rate and its bytes over the peak bandwidth,
    times the program's runs) over the device time spent under it.  Where
    the work goes with rows the run counted (``rows_counter``) and the count
    from shapes says for how many it stands (``rows``), it is rescaled to
    the rows the run computed."""
    inside, program = run.scope(scope), run.program("train")
    if inside is None or program is None or not inside["seconds"]:
        return None
    work = run.scope_work()
    if not work or scope not in work:
        return None
    work, peaks = work[scope], run.peaks()
    scale = 1.0
    if rows_counter is not None and work.get("rows") and rows_counter in run.counters:
        scale = run.counters[rows_counter] / work["rows"]
        run.notes[scope + "_rows"] = {"from_shapes": work["rows"],
                                      "counted": run.counters[rows_counter]}
    least = scale * max(work["flops"] / peaks["bf16_flops_per_s"],
                        work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * program["runs"] / inside["seconds"]
