"""One reader per per-layer metric: `read(run, label)` returns the number,
or None where this run gives it nothing to read. `run` is the harness's
Run: `run.facts` is what the runner recorded, `run.tracer.reduction` the
reduced device trace, `run.setup_meter` / `run.window_meter` the compile
counts, `run.peaks` the chip's published peaks. `label` is what follows the
first dot of the metric's name in BENCHMARK.json, or None.

What several readers share is here."""
from ..trace.reduce import MOSAIC


def traced_units(run):
    """How many steps (training) or dispatches (serving) the traced slice
    held."""
    red, facts = run.tracer.reduction, run.facts
    if facts.get("kind") == "train":
        return len(facts["traced_chains"]) * facts["steps_per_chain"]
    return red["spans"].get("serving.dispatch", 0) if red else 0


def kernel_seconds(run):
    """Device seconds of the Mosaic kernels in the traced slice. The Pallas
    calls of ops/pallas_attention.py are the only Mosaic kernels in these
    programs, and they carry no name of their own yet (the trace names them
    after the jitted function around them), so they are found by their
    kind: custom calls whose target is `tpu_custom_call`."""
    red = run.tracer.reduction
    return red["by_kind"].get(MOSAIC) if red else None


def roofline_floor(cost, peaks):
    """(seconds, which bound): the least time the chip could take for
    {"flops", "bytes"}, the larger of FLOPs over the peak rate and bytes
    over the peak bandwidth."""
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        "compute" if by_flops >= by_bytes else "memory"
