"""Scheduler and unified dispatch: the window's slowest tick, taken apart,
in milliseconds. `stats["slowest_ticks"][0]` is that tick's whole record as
the engine kept it: wall, CPU and collector's seconds, and per phase of the
tick the self time and the spans closed. The label names the part: `wait`,
`launch`, `fetch`, `sync_slot` the self time of that phase (labels as
`tick_host_ms.PHASES` maps them); `gc` the seconds the garbage collector
ran inside the tick, whatever phase it interrupted. A stall that is the
device's shows in `wait`; one that is the host's in a host phase; one that
is the collector's in `gc`.

Whether a stalled host phase computed or was off the CPU is the record's
`cpu_s` beside its `wall_s`, on the line `tick_tail_excess_share.py` says.
It is no metric here: the benchmark's host moves `time.thread_time()` in
steps of 10 ms, so ONE tick's CPU time is good to 10-20 ms: enough for a
stall of a second, noise for a clean run's slowest tick of 75-240 ms.

An engine that kept no records reads 0.0, not None (see `tick_wall_ms.py`)."""
from .tick_host_ms import PHASES
from .tick_wall_ms import window_stats


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    kept = st.get("slowest_ticks")
    if not kept:
        return 0.0
    rec = kept[0]
    if label == "gc":
        return 1e3 * rec["gc_s"]
    return 1e3 * rec["phases"][PHASES[label]]
