"""Model forward: the share of the window's dispatches whose dense
feed-forwards ran over their live rows alone, in per cent: the engine's
`stats["model_counters"]["live_rows"]`, cumulative (dispatches, dispatches
that took the compact form) counted in the program
(mxnet_tpu/models/hybrid.py `over_live_rows`: the compact form where a
dispatch's live rows fit an eighth of its grid). A low share means ticks
that are mostly prompt chunks.

An engine that reports stats and no such counter reads 0.0, not None, as
`tick_wall_ms.py` does and for its reason: the program of a parent commit
has no such form and took it never."""
from .tick_wall_ms import window_stats


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    dispatches, compact = (st.get("model_counters") or {}).get(
        "live_rows") or (0, 0)
    return 100.0 * compact / dispatches if dispatches else 0.0
