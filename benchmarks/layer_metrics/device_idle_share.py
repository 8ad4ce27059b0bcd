"""Device: the share of the traced slice, in per cent, in which no
instruction ran on the chip (mean over the chips used)."""


def read(run, label=None):
    red = run.tracer.reduction
    if not red or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
