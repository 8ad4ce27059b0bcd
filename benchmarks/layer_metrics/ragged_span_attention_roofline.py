"""Attention kernels, serving: the least time the chip could take for the
paged attention of the traced dispatches over the time the
ragged_span_attention kernel took in the trace, in per cent. What each
dispatch had to attend is rebuilt from the requests' own timelines
(runners/serve.py `rows_of_steps`) and costed by reference/<model>.py
`attention_cost`: the rows that carried a token, not the rows computed."""
from . import kernel_seconds, roofline_floor
from ..runners.serve import rows_of_steps


def read(run, label=None):
    secs, facts = kernel_seconds(run), run.facts
    if not secs or not run.peaks or not facts.get("traced_steps"):
        return None
    rows = rows_of_steps(facts["timelines"], facts["steps"],
                         facts["traced_steps"], facts["width"])
    if rows is None:
        return None
    cost = {"flops": 0, "bytes": 0}
    for step_rows in rows:
        c = facts["attention_cost"](facts["model_kwargs"], step_rows)
        cost = {k: cost[k] + c[k] for k in cost}
    floor, bound = roofline_floor(cost, run.peaks)
    run.say(f"ragged_span_attention: {secs * 1e3:.3f} ms over {len(rows)} "
            f"dispatches against a {bound}-bound floor of "
            f"{floor * 1e3:.3f} ms ({cost['flops'] / 1e9:.2f} GFLOP, "
            f"{cost['bytes'] / 1e9:.3f} GB needed)")
    return 100.0 * floor / secs
