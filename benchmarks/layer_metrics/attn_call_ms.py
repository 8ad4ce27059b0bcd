"""Attention kernels: the device time of the Mosaic kernels in the traced
slice, per training step or per dispatch, in milliseconds."""
from . import kernel_seconds, traced_units


def read(run, label=None):
    secs, n = kernel_seconds(run), traced_units(run)
    return secs / n * 1e3 if secs and n else None
