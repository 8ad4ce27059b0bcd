"""Linear-attention kernel, serving: the least time the chip could take for
the KDA chunk updates of the traced dispatches over the time the
kda_chunk_update kernel took in the trace, in per cent. What each dispatch
had to advance is rebuilt from the requests' own timelines
(runners/serve.py `rows_of_steps`) and costed by reference/<model>.py
`kda_cost`: each slot with work reads and writes its float32 state once,
and only live rows are counted. The earlier line says which bound."""
from . import roofline_floor
from .named_kernel_ms import kernel_seconds
from ..runners.serve import rows_of_steps

KERNEL, COST = "kda_chunk_update", "kda_cost"


def read(run, label=None, kernel=KERNEL, cost_fn=COST):
    secs, facts = kernel_seconds(run, kernel), run.facts
    if secs is None or not run.peaks or not facts.get("traced_steps"):
        return None
    if not secs:
        # a run whose engine names no kernel paths (named_kernel_ms.py)
        return 0.0
    ref = run.cell.module("reference", run.cell.config["reference"])
    if not hasattr(ref, cost_fn):
        return None
    rows = rows_of_steps(facts["timelines"], facts["steps"],
                         facts["traced_steps"], facts["width"])
    if rows is None:
        return None
    cost = {"flops": 0, "bytes": 0}
    for step_rows in rows:
        c = getattr(ref, cost_fn)(facts["model_kwargs"], step_rows)
        cost = {k: cost[k] + c[k] for k in cost}
    floor, bound = roofline_floor(cost, run.peaks)
    run.say(f"{kernel} kernel: {secs * 1e3:.3f} ms over {len(rows)} "
            f"dispatches against a {bound}-bound floor of "
            f"{floor * 1e3:.3f} ms ({cost['flops'] / 1e9:.2f} GFLOP, "
            f"{cost['bytes'] / 1e9:.3f} GB needed, by {cost_fn})")
    return 100.0 * floor / secs
