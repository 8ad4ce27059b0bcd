"""Unified dispatch: the share of the window that its slowest ticks cost
BEYOND a mean tick each, in percent: (the wall of `stats["slowest_ticks"]`,
up to eight, minus as many mean ticks) over `stats["tick_seconds"]["sum"]`.
About 0.1 in a clean run; 4 to 6 in a run that lost two or three seconds to
one tick. Set beside the 3% bound of `serve_tokens_per_s` it says whether
THIS run lost more than the bound to its slowest ticks.

The records it read (up to eight, slowest first: wall, CPU and collector's
seconds, phases, spans, queue) are also said whole on ONE earlier line, for
whoever reads the run. Readers run in a traced run only (`report.py`), so
only that run prints either.

An engine that kept neither reads 0.0, not None (see `tick_wall_ms.py`)."""
import json

from .tick_wall_ms import window_stats


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    ticks, kept = st.get("tick_seconds"), st.get("slowest_ticks")
    if not ticks or not kept or not ticks["count"]:
        return 0.0
    run.say(f"slowest ticks, seconds: {json.dumps(kept)}")
    mean = ticks["sum"] / ticks["count"]
    return 100.0 * (sum(rec["wall_s"] for rec in kept)
                    - len(kept) * mean) / ticks["sum"]
