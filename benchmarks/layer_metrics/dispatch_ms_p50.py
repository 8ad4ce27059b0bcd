"""Unified dispatch: the host's wall around one dispatch, from the
program's own `serving.dispatch` spans over the whole window, median, in
milliseconds. It holds the device's work and the one fetch that waits for
it, not the scheduler's tick around them."""
from .. import stats


def read(run, label=None):
    walls = [ev["dur"] * 1e3 for ev in run.facts.get("dispatches", ())]
    return stats.median(walls)
