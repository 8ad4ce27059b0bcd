"""KV cache: the device bytes of the slots' recurrent state (the fixed-size
per-slot leaves a model declares beside its KV pages: the engine's
`stats["recurrent_state_bytes"]`) over one chip's memory, in per cent. An
engine that reports none holds none: 0.0. Under `--check` there is no
chip, and the count is held to the published memory of the chip the cells
are written for (peaks.json's first entry): a count over a constant, not a
reading of a device."""
from .. import cells

CHIP = "TPU v5 lite"


def read(run, label=None):
    st = run.facts.get("engine_stats")
    if not st:
        return None
    peaks = run.peaks or cells.peaks(CHIP)
    return 100.0 * st.get("recurrent_state_bytes", 0) / peaks["hbm_bytes"]
