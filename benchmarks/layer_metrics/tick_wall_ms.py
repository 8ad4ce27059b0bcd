"""Unified dispatch: the window's scheduling ticks as the ENGINE timed them
(the wall of each `serving.step` span, observed into
`serving_tick_seconds{engine}` = `stats["tick_seconds"]`), in milliseconds.
The label names the reading: `p50` and `p99` from buckets a factor 2**0.25
apart, `max` exact. `max` is the stall when a run has one: a tick of one or
two seconds among seven hundred of tens of milliseconds, which no mean of
the window shows. `slowest_tick_ms.*` says which phase took it.

An engine that kept no such histogram reads 0.0, not None, as
`tick_host_ms.py` does and for its reason: the run made by hand in
tests/benchmarks/test_bench_units.py has three counters and nothing else,
and the program of a parent commit may lack the key."""


def window_stats(run):
    """The engine's stats at the window's close, or None for a run that
    has none or dispatched nothing: then there is nothing to read. The
    five readers of the tick's tail begin here."""
    st = run.facts.get("engine_stats")
    return st if st and st.get("decode_dispatches") else None


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    return 1e3 * st.get("tick_seconds", {}).get(label, 0.0)
