"""Lightweight span tracing: nesting, JSONL event log, profiler interplay.

`span(name)` is a context manager that (a) nests via a thread-local
stack, (b) records its duration into the labeled
`span_duration_seconds{name=...}` histogram, (c) appends a structured
event to an in-process ring buffer (and, when `enable_jsonl(path)` is
armed, to a JSON-lines file), and (d) forwards into
`jax.profiler.TraceAnnotation` — but ONLY while the mx.profiler device
trace is running, so spans line up with the XLA timeline without paying
annotation-construction cost (or importing jax at all) in normal
operation. That gating mirrors the `sys.modules` probe the op-dispatch
funnel uses (ops/registry._profiler_active): a process that never starts
a device trace never constructs an annotation.

Event schema (one JSON object per line):
    {"name", "ts" (unix seconds at exit), "dur" (seconds), "self"
     (seconds: dur minus what the spans nested directly inside it
     covered), "depth", "parent" (enclosing span name or null),
     "thread", ...attrs}

Self times partition their root: over a finished tree of spans the
`self` values sum to the root's `dur`. After exit the span object keeps
both as `dur` and `self_s`, for a caller that books them.

A span exited by a raising block records `status="error"` plus the
exception type under `"error"` — the exception itself propagates
untouched (`__exit__` returns False). Observers can subscribe to every
finished span with `add_event_hook(fn)` (the flight recorder's feed);
hook exceptions are swallowed, an observer must never break the host.

The garbage collector is counted where it runs: `install_gc_hook()` puts
ONE callback into `gc.callbacks` (idempotent; the serving engine calls it
when it is built) that keeps the running totals `gc_totals()` returns. A
span that takes them at its two ends knows what the collector took out
of it. A fault inside the callback is swallowed: it never reaches whoever
allocated the object that began the collection.
"""
from __future__ import annotations

import gc
import json
import sys
import threading
import time
from collections import deque

__all__ = ["span", "events", "clear_events", "enable_jsonl",
           "disable_jsonl", "add_event_hook", "remove_event_hook",
           "install_gc_hook", "gc_totals"]

_tls = threading.local()
_events_lock = threading.Lock()
_events = deque(maxlen=4096)
_jsonl = {"fh": None, "path": None}
_event_hooks = []


def _span_hist():
    # late import: instruments ↔ tracing have no cycle, but the default
    # registry lives in the package __init__ which imports this module
    from . import histogram
    return histogram("span_duration_seconds",
                     "wall time of telemetry.span ranges",
                     labelnames=("name",))


def _device_trace_running():
    prof = sys.modules.get("mxnet_tpu.profiler")
    return prof is not None and prof._state.get("jax_trace", False)


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class span:
    """with span("serving.decode_block", slot=3): ..."""

    __slots__ = ("name", "attrs", "dur", "self_s", "_ann", "_t0",
                 "_parent", "_depth", "_covered")

    def __init__(self, name, **attrs):
        self.name = name
        self.attrs = attrs
        self._ann = None

    def __enter__(self):
        st = _stack()
        self._parent = st[-1] if st else None
        self._depth = len(st)
        self._covered = 0.0             # seconds inside child spans
        st.append(self)
        if _device_trace_running():
            import jax
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.dur = dur = time.perf_counter() - self._t0
        self.self_s = dur - self._covered
        parent = self._parent
        if parent is not None:
            parent._covered += dur
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc_val, exc_tb)
            self._ann = None
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _span_hist().labels(self.name).observe(dur)
        ev = {"name": self.name, "ts": time.time(), "dur": dur,
              "self": self.self_s, "depth": self._depth,
              "parent": parent.name if parent is not None else None,
              "thread": threading.get_ident()}
        if exc_type is not None:
            # a raising block still records its span — tagged, so the
            # event log shows WHERE the stack unwound, not a silent gap
            ev["status"] = "error"
            ev["error"] = exc_type.__name__
        if self.attrs:
            ev.update(self.attrs)
        with _events_lock:
            _events.append(ev)
            fh = _jsonl["fh"]
            if fh is not None:
                try:
                    fh.write(json.dumps(ev) + "\n")
                    fh.flush()
                except Exception:
                    pass           # a full disk must not break serving
            hooks = list(_event_hooks)
        for fn in hooks:
            try:
                fn(ev)
            except Exception:
                pass               # observers must never break the host
        return False


def events():
    """The in-process span ring buffer (most recent 4096), oldest first."""
    with _events_lock:
        return list(_events)


def clear_events():
    with _events_lock:
        _events.clear()


def enable_jsonl(path):
    """Start appending every finished span to `path` as JSON lines."""
    with _events_lock:
        if _jsonl["fh"] is not None:
            _jsonl["fh"].close()
        _jsonl["fh"] = open(path, "a")
        _jsonl["path"] = path
    return path


def disable_jsonl():
    with _events_lock:
        if _jsonl["fh"] is not None:
            _jsonl["fh"].close()
        _jsonl["fh"] = None
        _jsonl["path"] = None


def add_event_hook(fn):
    """Call fn(event_dict) on every finished span (the flight
    recorder's subscription point). Exceptions in fn are swallowed."""
    with _events_lock:
        if fn not in _event_hooks:
            _event_hooks.append(fn)


def remove_event_hook(fn):
    with _events_lock:
        if fn in _event_hooks:
            _event_hooks.remove(fn)


# -- the garbage collector ----------------------------------------------------

# seconds inside collections so far, then collections of generation 0, 1, 2
_gc_totals = [0.0, 0, 0, 0]
# when the collection that is running began; the collector holds the
# interpreter lock from "start" to "stop", so one pending start is enough
_gc_began = [None]


def _on_gc(phase, info):
    try:
        if phase == "start":
            _gc_began[0] = time.perf_counter()
            return
        t0, _gc_began[0] = _gc_began[0], None
        if t0 is None:              # installed in the middle of one
            return
        _gc_totals[0] += time.perf_counter() - t0
        _gc_totals[1 + info["generation"]] += 1
    except Exception:
        pass        # never into the caller whose allocation began this


def install_gc_hook():
    """Count the garbage collector's runs and seconds from now on. Safe
    to call any number of times: one callback a process."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def gc_totals():
    """(seconds inside collections, collections of generation 0, 1, 2)
    since the hook was installed, never reset: read it at a span's two
    ends."""
    return tuple(_gc_totals)
