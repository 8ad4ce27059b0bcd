"""Expert kernel, serving: the least time the chip could take for the
grouped expert feed-forward of the traced dispatches over the time the
expert_ffn kernel took in the trace, in per cent. What a dispatch had to
compute is the expert layers' own count (expert_counters.py): pairs
computed and experts touched a dispatch, as the window's mean, times the
traced dispatches; reference/<model>.py `expert_cost` prices it (both
matrices of a touched expert read once a layer a dispatch, a pair's latent
row in and out, 4 x latent x hidden FLOPs a pair). The counters are
cumulative over the window and the runner fetches them once, at its end, so
the traced slice's own counts are the mean's: in a backlog every dispatch
carries about the same rows."""
from . import roofline_floor, traced_units
from .expert_counters import totals
from .moe_kernel_ms import kernel_seconds


def read(run, label=None):
    secs, t = kernel_seconds(run, "expert_ffn"), totals(run)
    if secs is None or t is None or not run.peaks:
        return None
    if not secs:
        # a run whose engine names no kernel paths (mosaic_kernel_ms.py)
        return 0.0
    ref = run.cell.module("reference", run.cell.config["reference"])
    n = traced_units(run)
    if not hasattr(ref, "expert_cost") or not t["dispatches"] or not n:
        return None
    # t sums the layers; dispatches is counted once a layer
    per = t["layers"] * n / t["dispatches"]
    cost = ref.expert_cost(run.facts["model_kwargs"], t["pairs"] * per,
                           t["experts_touched"] * per)
    floor, bound = roofline_floor(cost, run.peaks)
    run.say(f"expert_ffn kernel: {secs * 1e3:.3f} ms over {n} dispatches "
            f"against a {bound}-bound floor of {floor * 1e3:.3f} ms "
            f"({cost['flops'] / 1e9:.2f} GFLOP, {cost['bytes'] / 1e9:.3f} GB "
            f"needed, by expert_cost: {t['pairs'] * per / n:.0f} pairs and "
            f"{t['experts_touched'] * per / n:.0f} touched experts a "
            "dispatch over the expert layers)")
    return 100.0 * floor / secs
