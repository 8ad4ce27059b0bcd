"""Unified dispatch: what the garbage collector takes out of a tick, in mean
milliseconds a dispatch over the window: `stats["tick_gc_seconds"]`, the
seconds the collector ran between the two ends of the `serving.step` spans
(`serving_tick_gc_seconds_total{engine}`), over the dispatches. It is a part
of the host's serial milliseconds (`tick_host_ms.*`), in whatever phase the
collection fell.

An engine that counted none reads 0.0, not None (see `tick_wall_ms.py`)."""
from .tick_wall_ms import window_stats


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    return 1e3 * st.get("tick_gc_seconds", 0.0) / st["decode_dispatches"]
