"""Kernels, in a program whose Mosaic calls each carry a name: the device
time of the calls of ONE name in the traced slice, per dispatch, in
milliseconds. The trace names a Mosaic call after its `pallas_call`'s
`name`, and the metric's label is that name (or what it starts with):

    named_kernel_ms.kda_chunk_update        ops/kda.py
    named_kernel_ms.latent_span_attention   ops/pallas_attention.py
    named_kernel_ms.kv_page_write           ops/pallas_attention.py

Nothing is booked by exclusion, so a kernel the program gains later moves
none of these (mosaic_kernel_ms.py and moe_kernel_ms.py book every call
that is not one of theirs to the span kernel). A traced slice with no call
of the name gives None, and on the chip the harness then stops the run, as
mosaic_kernel_ms.py says; only a run whose engine reports no `kernel_paths`
at all reads 0.0: the run made by hand in tests/benchmarks/test_bench_units.py.
"""
from . import traced_units
from ..trace.reduce import MOSAIC


def kernel_seconds(run, name):
    """Device seconds of the Mosaic calls whose name starts with `name` in
    the traced slice; None where there is no reduced trace, or no such
    call in it."""
    if not name:
        raise ValueError("named_kernel_ms needs the call's name as its label")
    red = run.tracer.reduction
    if not red:
        return None
    secs = 0.0
    for label, s in red["by_op"].items():
        # "<name> <kind of instruction> <result shape>" (trace/reduce.py)
        call, _, rest = label.partition(" ")
        if rest.partition(" ")[0] == MOSAIC and call.startswith(name):
            secs += s
    if secs:
        return secs
    stats = run.facts.get("engine_stats") or {}
    return None if "kernel_paths" in stats else 0.0


def read(run, label=None):
    secs, n = kernel_seconds(run, label), traced_units(run)
    return secs / n * 1e3 if secs is not None and n else None
