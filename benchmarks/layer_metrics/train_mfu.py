"""Train step: model FLOP/s utilization in per cent: items a second a chip
(from the chain walls of this run) times the FLOPs a step must do for an
item (reference/<model>.py `flops_per_item`; recomputation not counted)
over the chip's published bf16 peak."""


def read(run, label=None):
    f = run.facts
    if f.get("kind") != "train" or not run.peaks:
        return None
    rate = f["items_per_step"] / f["step_s"] / f["chips"]
    return 100.0 * rate * f["flops_per_item"] \
        / run.peaks["bf16_flops_per_s"]
