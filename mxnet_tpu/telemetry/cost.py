"""Device-cost accounting: program cost registry, MFU/roofline gauges,
and compile attribution.

PRs 2 and 5 made the host side observable; this module makes the
*device economics* observable (docs/OBSERVABILITY.md "Device-cost
accounting"). Three pieces, one process-global program table:

  * **Program cost registry** — every jitted program the framework
    dispatches registers its XLA ``cost_analysis()`` (FLOPs, bytes
    accessed) keyed by a program signature string (``engine0/prefill/64``,
    ``engine0/decode/greedy``, ``train_step``). Combined with the
    measured per-dispatch wall time it publishes live MFU
    (``cost_mfu{program}``), achieved bandwidth, arithmetic intensity,
    and a compute-vs-memory-bound roofline classification per program.
  * **Compile attribution** — ``CostedFunction`` wraps a ``jax.jit``
    callable for one fixed signature: the first call times the full
    trace+lower+compile explicitly (AOT), extracts the cost analysis,
    and counts ``compiles_total{program}`` / ``compile_seconds_total
    {program}``; later calls run the compiled executable directly.
    Compile events feed registered hooks — the flight recorder
    subscribes so a *steady-state* retrace (shape churn after warmup)
    latches a dump with the offending program key.
  * **Peaks** — per-device peak FLOP/s and HBM bandwidth by device
    kind (public Google Cloud TPU system-architecture numbers), env-
    overridable with ``MXNET_TPU_PEAK_FLOPS`` / ``MXNET_TPU_PEAK_
    BANDWIDTH``. The ridge point (peak_flops / peak_bw) classifies
    each program: arithmetic intensity above the ridge is compute
    bound, below is memory bound. This is the ONE peak table: an
    accelerator kind it does not know is an error, and a CPU has no
    peak — MFU, bandwidth utilization and the roofline side are simply
    absent from a CPU run.

In-path cost per dispatch is a handful of instrument updates (~µs
against multi-ms dispatches); ``set_enabled(False)`` turns the in-path
accounting into a no-op for A/B runs (the AOT wrapping itself stays —
it is structural, not per-dispatch work).

Stdlib-only at import: jax is imported lazily inside ``peaks()`` (and
only when a device has necessarily been initialized by the caller).
"""
from __future__ import annotations

import math
import os
import threading
import time

from ..base import MXNetError

__all__ = ["CostedFunction", "ProgramCompileError", "register_program",
           "record_compile", "note_dispatch", "get", "report", "peaks",
           "device_peaks", "set_enabled", "enabled", "add_compile_hook",
           "remove_compile_hook", "reset_programs"]

_lock = threading.Lock()
_programs = {}             # program key -> _ProgramRecord
_compile_hooks = []
_enabled = True
_device = None             # the default jax device, asked for once
_peaks_published = None    # last (flops, bw) written to the gauges


# (device-kind substring, (peak bf16 FLOP/s, peak HBM bytes/s)).
# Sources: public Google Cloud TPU system-architecture pages
# (cloud.google.com/tpu/docs/system-architecture-tpu-vm and the per-
# generation pages, checked 2025): bf16 peak per chip v2 45, v3 123,
# v4 275, v5e 197, v5p 459, v6e/Trillium 918 TFLOP/s; HBM bandwidth v2
# 700, v3 900, v4 1228, v5e 819, v5p 2765, v6e 1640 GB/s.
# Ordered: more specific substrings first ("v5 lite" before "v5").
_PEAK_TABLE = (
    ("v5 lite", (197e12, 819e9)), ("v5litepod", (197e12, 819e9)),
    ("v5e", (197e12, 819e9)),
    ("v6 lite", (918e12, 1640e9)), ("v6e", (918e12, 1640e9)),
    ("v5p", (459e12, 2765e9)),
    ("v4", (275e12, 1228e9)),
    ("v5", (459e12, 2765e9)),
    ("v3", (123e12, 900e9)),
    ("v2", (45e12, 700e9)),
)


class ProgramCompileError(MXNetError):
    """Lowering or compiling `program` failed. Deterministic for a fixed
    signature — the same call fails the same way every time — so nothing
    catches it to retry or to fall back to another path."""

    def __init__(self, program, cause):
        super().__init__(f"program {program!r} failed to lower/compile: "
                         f"{type(cause).__name__}: {cause}")
        self.program = program


class _ProgramRecord:
    """One program's registered cost + accumulated compile/dispatch
    totals (mirrored onto labeled instruments; this object is the
    /compilez + report() source of truth)."""

    __slots__ = ("program", "flops", "bytes_accessed", "source",
                 "compiles", "compile_seconds", "dispatches",
                 "dispatch_seconds", "last_seconds", "last_compile_ts",
                 "shards")

    def __init__(self, program):
        self.program = program
        self.flops = None
        self.bytes_accessed = None
        self.source = None
        self.compiles = 0
        self.compile_seconds = 0.0
        self.dispatches = 0
        self.dispatch_seconds = 0.0
        self.last_seconds = None
        self.last_compile_ts = None
        self.shards = 1


_P = ("program",)
_metrics_cache = None


def _metrics():
    """Get-or-create the cost instrument family (lazy so importing
    telemetry stays declaration-free until cost accounting is used)."""
    global _metrics_cache
    if _metrics_cache is None:
        from . import counter, gauge
        _metrics_cache = {
            "compiles": counter(
                "compiles_total",
                "trace+lower+compile events per program signature", _P),
            "compile_seconds": counter(
                "compile_seconds_total",
                "wall seconds spent compiling, per program signature",
                _P),
            "dispatches": counter(
                "cost_dispatches_total",
                "cost-accounted dispatches per program", _P),
            "dispatch_seconds": counter(
                "cost_dispatch_seconds_total",
                "accumulated dispatch wall seconds per program", _P),
            "program_flops": gauge(
                "cost_program_flops",
                "XLA cost_analysis FLOPs of one dispatch of the "
                "program", _P),
            "program_bytes": gauge(
                "cost_program_bytes_accessed",
                "XLA cost_analysis bytes accessed by one dispatch", _P),
            "ai": gauge(
                "cost_arithmetic_intensity",
                "program FLOPs / bytes accessed (roofline x-axis)", _P),
            "compute_bound": gauge(
                "cost_compute_bound",
                "1 = arithmetic intensity above the device ridge point "
                "(compute bound), 0 = below (memory bound)", _P),
            "mfu": gauge(
                "cost_mfu",
                "model FLOPs utilization of the last dispatch "
                "(flops / wall / peak_flops)", _P),
            "achieved_flops": gauge(
                "cost_achieved_flops_per_sec",
                "program FLOPs / last dispatch wall", _P),
            "achieved_bw": gauge(
                "cost_achieved_bandwidth_bytes_per_sec",
                "program bytes accessed / last dispatch wall", _P),
            "peak_flops": gauge(
                "cost_peak_flops",
                "assumed per-chip peak FLOP/s (device table or "
                "MXNET_TPU_PEAK_FLOPS)"),
            "peak_bw": gauge(
                "cost_peak_bandwidth_bytes_per_sec",
                "assumed per-chip peak HBM bytes/s (device table or "
                "MXNET_TPU_PEAK_BANDWIDTH)"),
            "ridge": gauge(
                "cost_ridge_intensity",
                "device ridge point: peak_flops / peak_bandwidth "
                "(FLOPs per byte)"),
        }
    return _metrics_cache


# -- peaks ------------------------------------------------------------------

def device_peaks(device):
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one jax device from the
    table above; (None, None) for a CPU, which has no peak worth the
    name. An accelerator the table does not know raises — a guessed peak
    would put a wrong MFU under a real device's name."""
    if device.platform == "cpu":
        return None, None
    kind = str(device.device_kind)
    low = kind.lower()
    for sub, vals in _PEAK_TABLE:
        if sub in low:
            return vals
    raise MXNetError(
        f"no peak FLOP/s / bandwidth known for device kind {kind!r} "
        f"(platform {device.platform!r}): add it to "
        "telemetry.cost._PEAK_TABLE with its source, or set "
        "MXNET_TPU_PEAK_FLOPS and MXNET_TPU_PEAK_BANDWIDTH")


def peaks():
    """(peak_flops, peak_bandwidth_bytes_per_sec, device_kind) of the
    default device; the two peaks are None on a CPU.

    Env overrides are read every call (tests, hardware the table does
    not know) and win over the table; the backend is asked for its
    device once."""
    global _device, _peaks_published
    if _device is None:
        import jax
        _device = jax.devices()[0]
    flops = float(os.environ.get("MXNET_TPU_PEAK_FLOPS", 0) or 0) or None
    bw = float(os.environ.get("MXNET_TPU_PEAK_BANDWIDTH", 0) or 0) or None
    if not (flops and bw):
        table = device_peaks(_device)
        flops, bw = flops or table[0], bw or table[1]
    if flops and bw and _peaks_published != (flops, bw):
        m = _metrics()                      # hot path: publish on change
        m["peak_flops"].set(flops)
        m["peak_bw"].set(bw)
        m["ridge"].set(flops / bw)
        _peaks_published = (flops, bw)
    return flops, bw, str(_device.device_kind)


# -- enable/disable the in-path accounting ----------------------------------

def set_enabled(flag):
    """Gate the per-dispatch accounting (note_dispatch becomes a no-op
    returning None). Compile attribution and program registration are
    one-time events and stay on."""
    global _enabled
    _enabled = bool(flag)


def enabled():
    return _enabled


# -- the program table ------------------------------------------------------

def _record(program):
    rec = _programs.get(program)
    if rec is None:
        rec = _programs.setdefault(program, _ProgramRecord(program))
    return rec


def register_program(program, flops=None, bytes_accessed=None,
                     source="xla", shards=1):
    """Register (or refresh) a program's static cost. `flops`/`bytes_
    accessed` of ONE dispatch — the WHOLE-MODEL figures, summed over
    partitions for an SPMD program (callers extracting from a sharded
    executable multiply the per-partition cost_analysis() up before
    registering; CostedFunction(shards=N) does this). `shards` is the
    partition count: note_dispatch divides by it so the per-chip MFU /
    bandwidth gauges stay honest under tp>1 while `.flops` keeps
    feeding whole-model goodput counters. Non-finite / non-positive
    values are treated as unknown (backends that don't report costs).
    Returns the record."""
    def _clean(v):
        if v is None:
            return None
        v = float(v)
        return v if math.isfinite(v) and v > 0 else None

    flops, bytes_accessed = _clean(flops), _clean(bytes_accessed)
    with _lock:
        rec = _record(program)
        if flops is not None:
            rec.flops = flops
        if bytes_accessed is not None:
            rec.bytes_accessed = bytes_accessed
        rec.source = source
        rec.shards = max(int(shards), 1)
        flops, bytes_accessed = rec.flops, rec.bytes_accessed
    m = _metrics()
    if flops is not None:
        m["program_flops"].labels(program).set(flops)
    if bytes_accessed is not None:
        m["program_bytes"].labels(program).set(bytes_accessed)
    if flops is not None and bytes_accessed is not None:
        ai = flops / bytes_accessed
        pf, pb, _ = peaks()
        m["ai"].labels(program).set(ai)
        if pf and pb:
            m["compute_bound"].labels(program).set(
                1.0 if ai >= pf / pb else 0.0)
    return get(program)


def record_compile(program, seconds, steady=False):
    """Count one trace+lower+compile of `program` and fan the event out
    to the compile hooks (the flight recorder's retrace-storm detector
    rides here). `steady=True` marks a compile AFTER the owner declared
    steady state — shape churn that should not happen."""
    seconds = float(seconds)
    with _lock:
        rec = _record(program)
        rec.compiles += 1
        rec.compile_seconds += seconds
        rec.last_compile_ts = time.time()
        hooks = list(_compile_hooks)
    m = _metrics()
    m["compiles"].labels(program).inc()
    m["compile_seconds"].labels(program).inc(seconds)
    ev = {"program": program, "seconds": seconds, "steady": bool(steady),
          "ts": time.time()}
    for fn in hooks:
        try:
            fn(ev)
        except Exception:
            pass               # a broken subscriber must not break dispatch
    return ev


def note_dispatch(program, seconds):
    """Attribute one measured dispatch wall to `program`; publishes the
    live MFU / achieved-bandwidth gauges when the program has a
    registered cost. Returns the program record (None when accounting
    is disabled) — callers use `.flops` for goodput counters."""
    if not _enabled:
        return None
    seconds = max(float(seconds), 1e-9)
    with _lock:
        rec = _record(program)
        rec.dispatches += 1
        rec.dispatch_seconds += seconds
        rec.last_seconds = seconds
        flops, nbytes = rec.flops, rec.bytes_accessed
        sh = rec.shards or 1
    m = _metrics()
    m["dispatches"].labels(program).inc()
    m["dispatch_seconds"].labels(program).inc(seconds)
    # registered cost is whole-model; the gauges compare against ONE
    # chip's peak, so a tp=N program's achieved figures divide by the
    # shard count (each chip only did 1/N of the FLOPs in that wall)
    if flops is not None:
        pf, _, _ = peaks()
        if pf:
            m["mfu"].labels(program).set(flops / seconds / pf / sh)
        m["achieved_flops"].labels(program).set(flops / seconds / sh)
        # re-assert the static gauge so a telemetry.reset() between
        # bench rounds heals on the next dispatch (set only on change
        # would read a lock anyway; one blind set is the same cost)
        m["program_flops"].labels(program).set(flops)
    if nbytes is not None:
        m["achieved_bw"].labels(program).set(nbytes / seconds / sh)
        m["program_bytes"].labels(program).set(nbytes)
    return rec


def get(program):
    """Snapshot dict of one program's record (None when unknown)."""
    with _lock:
        rec = _programs.get(program)
        if rec is None:
            return None
        return _snap(rec)


def _snap(rec):
    out = {k: getattr(rec, k) for k in _ProgramRecord.__slots__}
    sh = rec.shards or 1
    if rec.flops and rec.bytes_accessed:
        out["arithmetic_intensity"] = rec.flops / rec.bytes_accessed
    if rec.flops and rec.last_seconds:
        pf, pb, _ = peaks()
        if pf:
            out["mfu"] = rec.flops / rec.last_seconds / pf / sh
        if pb and rec.bytes_accessed:
            out["bandwidth_util"] = (rec.bytes_accessed
                                     / rec.last_seconds / pb / sh)
    return out


def report():
    """The /compilez + `dump_telemetry --cost` view: every program's
    registered cost, roofline placement, compile attribution and
    dispatch totals, plus the device peaks (None on a CPU, and then no
    program carries `mfu`, `bandwidth_util` or `bound`)."""
    pf, pb, kind = peaks()
    with _lock:
        progs = {p: _snap(r) for p, r in sorted(_programs.items())}
    ridge = pf / pb if pf and pb else None
    for snap in progs.values():
        ai = snap.get("arithmetic_intensity")
        if ai is not None and ridge is not None:
            snap["bound"] = "compute" if ai >= ridge else "memory"
    return {"device_kind": kind, "peak_flops": pf,
            "peak_bandwidth_bytes_per_sec": pb,
            "ridge_intensity": ridge, "programs": progs}


def reset_programs():
    """Forget every program record (tests / between bench rounds that
    rebuild their engines). Instruments are left to telemetry.reset()."""
    with _lock:
        _programs.clear()


# -- compile hooks ----------------------------------------------------------

def add_compile_hook(fn):
    """fn(event_dict) runs on every record_compile (the flight recorder
    subscribes for steady-state retrace detection)."""
    with _lock:
        if fn not in _compile_hooks:
            _compile_hooks.append(fn)


def remove_compile_hook(fn):
    with _lock:
        try:
            _compile_hooks.remove(fn)
        except ValueError:
            pass


# -- the AOT wrapper --------------------------------------------------------

def _cost_from_compiled(compiled):
    """(flops, bytes_accessed) from an XLA Compiled, None-safe across
    backend/version variations (list-of-dicts vs dict, missing keys,
    sentinel -1 values)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not ca:
        return None, None
    d = dict(ca)
    return d.get("flops"), d.get("bytes accessed")


class CostedFunction:
    """AOT wrapper around a ``jax.jit`` function for ONE fixed call
    signature: the first call explicitly lowers + compiles (timed into
    ``compiles_total{program}`` / ``compile_seconds_total{program}``),
    registers the program's ``cost_analysis()`` FLOPs and bytes, and
    caches the compiled executable; every later call runs the
    executable directly — same arguments, same donation semantics.

    ``steady_fn`` (optional, ``() -> bool``): when it returns True at
    compile time the compile event is flagged *steady* — the flight
    recorder treats a steady compile as a retrace storm and latches a
    dump. Owners flip it after warmup (``ServingEngine.mark_warm()``).

    ``cost_scale``: multiplier applied to the extracted FLOPs/bytes
    before registration. XLA's HloCostAnalysis counts a while/scan body
    ONCE regardless of trip count, so a program that runs K chained
    steps per dispatch (the serving engine's K-step decode scan) must
    pass its trip count here for the per-dispatch cost to be honest.

    ``shards``: SPMD partition count of the program. ``cost_analysis()``
    on a sharded executable reports PER-PARTITION figures, so they are
    multiplied by `shards` before registration (the registry holds
    whole-model cost) and `note_dispatch` divides its per-chip gauges
    back down — `cost_mfu{program}` stays an honest fraction of ONE
    chip's peak at any tp.

    A failed lower/compile raises ProgramCompileError naming the
    program; nothing is cached, so the next call fails the same way."""

    __slots__ = ("_fn", "program", "_steady_fn", "_call", "_cost_scale",
                 "_shards")

    def __init__(self, fn, program, steady_fn=None, cost_scale=1.0,
                 shards=1):
        self._fn = fn
        self.program = str(program)
        self._steady_fn = steady_fn
        self._call = None
        self._cost_scale = float(cost_scale)
        self._shards = max(int(shards), 1)

    def __call__(self, *args):
        call = self._call
        if call is None:
            t0 = time.perf_counter()
            try:
                call = self._fn.lower(*args).compile()
            except Exception as e:
                raise ProgramCompileError(self.program, e) from e
            flops, nbytes = _cost_from_compiled(call)
            dt = time.perf_counter() - t0
            self._call = call
            s = self._cost_scale * self._shards
            register_program(self.program,
                             flops * s if flops else flops,
                             nbytes * s if nbytes else nbytes,
                             shards=self._shards)
            steady = False
            if self._steady_fn is not None:
                try:
                    steady = bool(self._steady_fn())
                except Exception:
                    steady = False
            record_compile(self.program, dt, steady=steady)
        return call(*args)
