"""Kernels, in a program with three kinds of Mosaic kernel: the device time
of ONE kind in the traced slice, per dispatch, in milliseconds. The trace
names a Mosaic call after its `pallas_call`'s `name`, or after the jit
around it where it has none (`%unified.N`), so:

    expert_ffn  the calls named `expert_ffn` (ops/moe.py)
    span        the Mosaic calls with neither that name nor
                `ssd_chunk_update`'s: ops/pallas_attention.py's span
                kernel, which carries no name of its own

(mosaic_kernel_ms.span books every call that is not `ssd_chunk_update` to
the span kernel, the expert kernel's too: it is not listed for a cell with
experts.) A traced slice with no call of the kind gives None, and on the
chip the harness then stops the run, as mosaic_kernel_ms.py says; only a
run whose engine reports no `kernel_paths` at all reads 0.0.
"""
from . import traced_units
from .mosaic_kernel_ms import SSD_NAME
from ..trace.reduce import MOSAIC

KINDS = ("expert_ffn", "span")
EXPERT_NAME = "expert_ffn"


def kernel_seconds(run, kind):
    """Device seconds of the Mosaic calls of one kind in the traced slice;
    None where there is no reduced trace, or none of the kind in it."""
    if kind not in KINDS:
        raise ValueError(f"no Mosaic kernel of kind {kind!r}: {KINDS}")
    red = run.tracer.reduction
    if not red:
        return None
    secs = 0.0
    for label, s in red["by_op"].items():
        # "<name> <kind of instruction> <result shape>" (trace/reduce.py)
        name, _, rest = label.partition(" ")
        if rest.partition(" ")[0] != MOSAIC or name.startswith(SSD_NAME):
            continue
        if name.startswith(EXPERT_NAME) == (kind == "expert_ffn"):
            secs += s
    if secs:
        return secs
    stats = run.facts.get("engine_stats") or {}
    return None if "kernel_paths" in stats else 0.0


def read(run, label=None):
    secs, n = kernel_seconds(run, label), traced_units(run)
    return secs / n * 1e3 if secs is not None and n else None
