"""Attention kernels, training: the least time the chip could take for the
attention of the traced steps (reference/<model>.py `attention_cost`) over
the time the fused_attention kernels took in the trace, in per cent."""
from . import kernel_seconds, roofline_floor, traced_units


def read(run, label=None):
    secs, n = kernel_seconds(run), traced_units(run)
    if not secs or not n or not run.peaks:
        return None
    floor, bound = roofline_floor(run.facts["attention_cost"], run.peaks)
    run.say(f"fused_attention: {secs / n * 1e3:.3f} ms a step against a "
            f"{bound}-bound floor of {floor * 1e3:.3f} ms")
    return 100.0 * floor * n / secs
