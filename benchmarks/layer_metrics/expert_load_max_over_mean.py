"""Expert layers: the rows of the fullest held expert over the mean rows a
held expert, summed over the window's dispatches and layers (sum of the
largest groups over sum of the mean groups). 1.0 is an even choice; the
dropless layer computes whatever it is, and the grouped kernel's row tiles
fill worse the further it is from 1. An engine without experts reads 0.0."""
from .expert_counters import totals


def read(run, label=None):
    t = totals(run)
    if t is None:
        return None
    return t["largest_group"] * t["held"] / t["pairs"] if t["pairs"] else 0.0
