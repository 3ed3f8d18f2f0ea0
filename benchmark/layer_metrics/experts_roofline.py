"""The routed experts' products' share of their roofline (scope ``experts``),
for the rows the run's own counter says fell on held experts
(``counter_rows_held``), forward and backward.  Since PR 60 the grouped
kernels run the blocks of 128 rows that hold a row and no others (blocks of
16 and float32 products still every block), so the share says how full the
blocks that ran were, a held expert's last one with them, and what the route
and the skipped steps cost beside the products."""

from benchmark import scopes


def read(run):
    return scopes.roofline(run, "experts", rows_counter="counter_rows_held")
