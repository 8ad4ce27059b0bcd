"""What a runner is handed and what it hands back: the cell, the seed, the
devices, the clock of set-up and window, the tracer, and the places for its
results. run.py makes one and prints what it holds."""
import contextlib
import sys
import time

from . import cells, meter
from .trace import Tracer


class Run:
    def __init__(self, cell, seed, seconds, trace, devices, t_start,
                 keep_trace=None):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.devices = bool(trace), devices
        self.keep_trace = keep_trace
        self.t_start = t_start          # the process's first instant
        self.tracer = Tracer()
        self.meter = meter.CompileMeter()
        self.phases = {}                # set-up, seconds by phase
        self.result = {}                # correct, attempted, failed
        self.end_to_end = {}            # name -> value, from the runner
        self.facts = {}                 # what the per-layer readers read
        self.t_open = self.t_close = None
        self.peaks = None if cell.tiny \
            else cells.peaks(devices[0].device_kind)

    def say(self, text):
        """An earlier line of the output: for people, never parsed."""
        print(f"[bench] {text}", flush=True)

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) \
            + time.perf_counter() - t0

    def open_window(self):
        """Set-up ends and the measured window begins, now."""
        self.setup_meter = self.meter.snapshot()
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_start
        self.phases["import"] = self.setup_s - sum(self.phases.values())

    def window_left(self):
        return self.seconds - (time.perf_counter() - self.t_open)

    def close_window(self):
        self.t_close = time.perf_counter()
        self.held_in_window = meter.bytes_in_use(self.devices)
        self.window_meter = self.meter.since(self.setup_meter)
        if self.window_meter["compiles"]:
            self.say(f"WRONG: {self.window_meter['compiles']} programs were "
                     "compiled inside the window")
            self.result["compiled_in_window"] = True

    def device(self):
        return meter.device_report(self.devices, self.held_in_window,
                                   self.facts.get("program_temp_bytes", 0))


def fail(message, code=3):
    """No result line, a reason on stderr, a code other than 0."""
    print(f"benchmarks/run.py: {message}", file=sys.stderr)
    sys.exit(code)
