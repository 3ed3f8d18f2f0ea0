"""Share of the train program's device time under the scope ``route``:
scores, top-k, the sort by expert, the gathers that fill and empty the row
buffer, the gates."""

from benchmark import scopes


def read(run):
    return scopes.step_share(run, "route")
