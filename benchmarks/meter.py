"""What the run costs beside its window: compiles (jax.monitoring, copied
from chip_smoke.py's CompileMeter), the device as JAX reports it, and its
peak memory."""
import jax


class CompileMeter:
    """Every compile of the process since this was made: seconds in the
    backend compiler (where a persistent-cache hit shows as a short read),
    seconds tracing and lowering (which no cache saves), how many programs
    went to the backend, and how many of those the cache served."""

    FIELDS = ("compile_s", "trace_lower_s", "compiles", "cache_hits")

    def __init__(self):
        self.compile_s = self.trace_lower_s = 0.0
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles += 1
        elif event.startswith("/jax/core/compile/"):
            self.trace_lower_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return {f: getattr(self, f) for f in self.FIELDS}

    def since(self, before):
        return {f: getattr(self, f) - before[f] for f in self.FIELDS}


def bytes_in_use(devices):
    """What the allocator has handed out on each device, now. A backend
    without memory statistics (the CPU of `--check`) gives zeros."""
    return [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices]


def allocator_peak(devices):
    """The allocator's own high-water mark on the fullest of `devices`,
    over the whole process: `memory_stats()["peak_bytes_in_use"]`."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def device_report(devices, held_in_window, program_temp_bytes):
    """The `device` object of the result line.

    `memory_peak_bytes` is what the fullest chip held while the window's
    program ran: the arrays the allocator had handed out when the window
    closed (`held_in_window`: weights, optimizer state, pages, outputs),
    which were all alive during every run of that program, plus the
    temporaries that program allocates for each of its runs
    (`memory_analysis().temp_size_in_bytes` of the executable: on this
    runtime `memory_stats()` does not count them, and they are most of a
    training step's memory, its saved activations). The two are alive at
    the same instant, so the sum is a footprint and not two peaks added up;
    the result's earlier lines and the per-layer metrics `peak_hbm_share`
    and `program_temp_hbm_share` give each part alone. Without memory
    statistics it is 0."""
    held = max(held_in_window, default=0)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": held + program_temp_bytes if held else 0}
