"""Unified dispatch: the part of the host's own milliseconds a tick that the
serving thread spent OFF the CPU, in mean milliseconds a dispatch: the
ticks' wall outside `dispatch.wait` (the host blocked on the device by
design; `stats["tick_phase_seconds"]` over every other phase) minus the
ticks' CPU time (`stats["tick_cpu_seconds"]`, `time.thread_time()` at the
two ends of `serving.step`). It is the waiting on the runtime inside the
host's serial part: the copies of the nine small outputs, the slot uploads.
Batching those crossings would take it; the rest of the host's milliseconds
is the interpreter working.

The CPU time is the whole tick's, so what the thread computes INSIDE
`dispatch.wait` (nine `block_until_ready` calls: 0.07-0.39 ms a tick when
that span had a clock of its own, PERF.md section 6, PR 37) is taken off
too, and the reading is low by that. The benchmark's host moves the
thread's clock in steps of 10 ms: a window's sum over hundreds of ticks
averages them out, one tick's difference does not.

An engine that kept no CPU time reads 0.0, not None (see
`tick_wall_ms.py`)."""
from .tick_wall_ms import window_stats


def read(run, label=None):
    st = window_stats(run)
    if st is None:
        return None
    wall, cpu = st.get("tick_phase_seconds"), st.get("tick_cpu_seconds")
    if not wall or cpu is None:
        return 0.0
    host = sum(s for ph, s in wall.items() if ph != "dispatch.wait")
    return 1e3 * (host - cpu) / st["decode_dispatches"]
