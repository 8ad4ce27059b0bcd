"""Model forward: the time the device was busy in the traced slice, over
the training steps or the dispatches it held, in milliseconds."""
from . import traced_units


def read(run, label=None):
    red, n = run.tracer.reduction, traced_units(run)
    return red["busy_s"] / n * 1e3 if red and n else None
