"""What the expert layers counted in the window: the engine's
`stats["model_counters"]["moe"]`, a row an expert layer of cumulative
(dispatches, live rows, pairs computed, experts touched, largest group)
(mxnet_tpu/models/nemotron_h.py MOE_COUNTERS), summed over the layers, and
how many experts a layer holds. Shared by the readers of those counters;
no metric of its own."""

COLUMNS = ("dispatches", "rows", "pairs", "experts_touched", "largest_group")


def totals(run):
    """{column: sum over the expert layers}, plus `held` and `layers`; None
    where the run has no engine counters at all; zeros for an engine that
    counts no experts (it has none)."""
    st = run.facts.get("engine_stats")
    if not st:
        return None
    rows = (st.get("model_counters") or {}).get("moe") or []
    out = {name: sum(r[i] for r in rows) for i, name in enumerate(COLUMNS)}
    kw = run.facts.get("model_kwargs") or {}
    out["held"] = (kw.get("held_experts") or (0, kw.get("num_experts", 0)))[1]
    out["layers"] = len(rows)
    return out
