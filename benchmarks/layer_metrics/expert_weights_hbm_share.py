"""Model: the device bytes of the routed experts this chip holds (the
engine's `stats["expert_weight_bytes"]`, from the model's `state_spec()`)
over one chip's memory, in per cent. An engine that reports none holds
none: 0.0. Under `--check` the count is held to the published memory of the
chip the cells are written for, as recurrent_state_hbm_share.py does."""
from .. import cells
from .recurrent_state_hbm_share import CHIP


def read(run, label=None):
    st = run.facts.get("engine_stats")
    if not st:
        return None
    peaks = run.peaks or cells.peaks(CHIP)
    return 100.0 * st.get("expert_weight_bytes", 0) / peaks["hbm_bytes"]
