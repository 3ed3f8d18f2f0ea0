"""Share of the traced window in which no operation ran on the chip
(averaged over the chips used): 1 - busy / window, from the device trace."""


def read(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced["idle_s"] / run.reduced["window_s"]
