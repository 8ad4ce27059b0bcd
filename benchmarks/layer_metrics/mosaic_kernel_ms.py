"""Kernels, in a program with more than one kind of Mosaic kernel: the
device time of ONE kind in the traced slice, per dispatch, in milliseconds.
The trace names a Mosaic call after its `pallas_call`'s `name`, or after
the jit around it where it has none (`%unified.N`), so:

    ssd_chunk   the calls named `ssd_chunk_update` (ops/ssm.py)
    span        every other Mosaic call: ops/pallas_attention.py's span
                kernel, which carries no name of its own

A traced slice with no call of the kind gives None, and on the chip the
harness then stops the run: the engine's `kernel_paths` counter says the
program was traced with the kernel (or with its dense form, which is the
finding), and a time of nothing would read as the best of all. Only a run
whose engine reports no `kernel_paths` at all reads 0.0: the run made by
hand in tests/benchmarks/test_bench_units.py, which gives every cell
GPT-2's trace and three counters and holds the result line to the listed
metrics exactly (PERF.md section 7 asks the next benchmark PR to change
that); no engine of this program is without the counter.
"""
from . import traced_units
from ..trace.reduce import MOSAIC

KINDS = ("ssd_chunk", "span")
SSD_NAME = "ssd_chunk_update"


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
        if rest.partition(" ")[0] == MOSAIC \
                and name.startswith(SSD_NAME) == (kind == "ssd_chunk"):
            secs += s
    if secs:
        return secs
    stats = run.facts.get("engine_stats") or {}
    return None if "kernel_paths" in stats else 0.0


def read(run, label=None):
    secs, n = kernel_seconds(run, label), traced_units(run)
    return secs / n * 1e3 if secs is not None and n else None
