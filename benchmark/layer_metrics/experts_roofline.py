"""The routed experts' products' share of their roofline (scope ``experts``),
for the rows the run's own counter says fell on held experts
(``counter_rows_held``), forward and backward.  The program computes every
block of its row buffer, filled or not, so the share also says how full the
buffer was."""

from benchmark import scopes


def read(run):
    return scopes.roofline(run, "experts", rows_counter="counter_rows_held")
