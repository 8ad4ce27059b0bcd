"""Device: the allocator's own peak on the fullest chip over the whole
process (`memory_stats()["peak_bytes_in_use"]`: the arrays the process
held, set-up included) over the chip's memory, in per cent. What a running
program allocates for itself is not in it: program_temp_hbm_share."""
from .. import meter


def read(run, label=None):
    peak = meter.allocator_peak(run.devices)
    if not peak or not run.peaks:
        return None
    return 100.0 * peak / run.peaks["hbm_bytes"]
