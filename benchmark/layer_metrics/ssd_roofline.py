"""The selective-state scan's share of its roofline: the chunked scan's
required operations and bytes (``flops/<family>.py`` ``scope_work``, scope
``ssd``) over the device time under that scope, forward and backward."""

from benchmark import scopes


def read(run):
    return scopes.roofline(run, "ssd")
