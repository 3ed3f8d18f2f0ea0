"""Share of the train program's device time under the scope ``ssd``: the
chunked selective-state scan of every Mamba-2 layer, without its
projections."""

from benchmark import scopes


def read(run):
    return scopes.step_share(run, "ssd")
