"""Taking a device trace of a slice of the window, and reducing it."""
import contextlib
import glob
import os
import shutil
import tempfile

import jax

from . import reduce as _reduce


class Tracer:
    """Host spans that reach the profiler while a trace runs and cost a
    comparison while none does, and the one traced slice of a run."""

    def __init__(self):
        self.running = False
        self.reduction = None       # set when the traced slice has ended

    def span(self, name, **attrs):
        if not self.running:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name, **attrs)

    @contextlib.contextmanager
    def slice(self, keep_dir=None):
        """Trace what runs inside. The trace is started through
        mx.profiler, so that the program's own telemetry spans
        (`serving.dispatch`) are forwarded into it. It is written under
        TMPDIR and removed once reduced; `keep_dir` keeps a copy of the
        `.xplane.pb` there for reading by hand."""
        import mxnet_tpu as mx
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        mx.profiler.set_config(trace_dir=tmp, profile_imperative=False,
                               aggregate_stats=False)
        mx.profiler.set_state("run")
        self.running = True
        try:
            with jax.profiler.TraceAnnotation(_reduce.WINDOW_SPAN):
                yield
        finally:
            self.running = False
            mx.profiler.set_state("stop")
            mx.profiler.set_config(trace_dir=None)
            try:
                found = glob.glob(os.path.join(
                    tmp, "plugins", "profile", "*", "*.xplane.pb"))
                if len(found) != 1:
                    raise RuntimeError(
                        f"expected one .xplane.pb under {tmp}, found {found}")
                if keep_dir:
                    os.makedirs(keep_dir, exist_ok=True)
                    shutil.copy(found[0], keep_dir)
                self.reduction = _reduce.reduce(_reduce.load(found[0]))
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
