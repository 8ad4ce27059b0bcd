"""Expert layers: (row, held expert) pairs computed per live row and expert
layer, over the window. A row chooses top_k of all the experts; the share
of them this chip holds falls to it (22 x 128 / 512 = 5.5 where the choice
is even). Dead rows of the fixed-shape dispatch have no pairs: that is what
the count checks. An engine without experts reads 0.0."""
from .expert_counters import totals


def read(run, label=None):
    t = totals(run)
    if t is None:
        return None
    return t["pairs"] / t["rows"] if t["rows"] else 0.0
