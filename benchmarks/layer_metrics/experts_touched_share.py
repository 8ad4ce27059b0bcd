"""Expert layers: of the experts a layer holds, the share, in per cent,
that got at least one row in a dispatch, over the window's dispatches and
layers. Each touched expert's two matrices are streamed from HBM once a
dispatch, so this is the share of the expert weights a dispatch reads. An
engine without experts reads 0.0."""
from .expert_counters import totals


def read(run, label=None):
    t = totals(run)
    if t is None:
        return None
    slots = t["dispatches"] * t["held"]
    return 100.0 * t["experts_touched"] / slots if slots else 0.0
