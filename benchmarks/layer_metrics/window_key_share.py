"""KV cache: of the key positions the window layers' rows would have read
as full layers (every page from the first of the sequence to the row's
own), the share that their window calls visit (the pages from the first
query's first key in the window to the last query's page), in per cent:
the engine's `stats["model_counters"]["window_keys"]`, cumulative [pages
visited, pages of the whole context] summed over the window layers
(mxnet_tpu/models/trinity.py). What is not visited is not read: the window
kernel skips the pages before the window, it does not mask them.

An engine that reports stats and no such counter reads 0.0, not None, as
`compact_dispatch_share.py` does and for its reason."""
from .tick_wall_ms import window_stats


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    visited, whole = (st.get("model_counters") or {}).get(
        "window_keys") or (0, 0)
    return 100.0 * visited / whole if whole else 0.0
