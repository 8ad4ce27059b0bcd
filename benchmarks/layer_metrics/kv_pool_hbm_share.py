"""KV cache: the device bytes of the page pools as allocated (the engine's
`stats["kv_pool_bytes"]`: K and V, or the one pool of a model whose page
row has no head axis, padding counted) over one chip's memory, in per cent,
beside recurrent_state_hbm_share. An engine that does not report the count
reads 0.0, as that reader does for an engine without recurrent state (the
run made by hand in tests/benchmarks/test_bench_units.py reports three
counters and is held to every listed metric). Under `--check` the count is held to the published memory of
the chip the cells are written for, as recurrent_state_hbm_share.py does."""
from .. import cells
from .recurrent_state_hbm_share import CHIP


def read(run, label=None):
    st = run.facts.get("engine_stats")
    if not st:
        return None
    peaks = run.peaks or cells.peaks(CHIP)
    return 100.0 * st.get("kv_pool_bytes", 0) / peaks["hbm_bytes"]
