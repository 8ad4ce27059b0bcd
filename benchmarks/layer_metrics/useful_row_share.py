"""Unified dispatch: of the rows the fixed-shape program computed in the
window (dispatches x slots x width), the share, in per cent, that carried a
prompt token or produced an output token. From the engine's counters."""


def read(run, label=None):
    st = run.facts.get("engine_stats")
    if not st or not st["decode_dispatches"]:
        return None
    rows = st["decode_dispatches"] * run.facts["slots"] * run.facts["width"]
    return 100.0 * (st["prefill_tokens"] + st["tokens_emitted"]) / rows
