"""How unevenly the router loads the experts held: the fullest held expert's
rows over the mean of the held experts' rows, in the worst expert layer of a
step, mean over the window's steps.  1 is an even load; the grouped products
walk whole tiles of rows, so the fullest expert sets what a step costs once
the load is uneven.  From the program's own counter (the step metric
``moe_load``, which the family lifts out of the model's state)."""

from benchmark import lfm2_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ratio", "lower", "samples_per_s"


def compute(ctx):
    cfg = lfm2_flops.cell_config(ctx.workload)
    counts = lfm2_flops.load_counts(ctx.step_metrics, cfg)
    if counts is None:
        return None
    held = lfm2_flops.held(cfg, counts).astype(float)
    worst = (held.max(-1) / held.mean(-1).clip(min=1e-30)).max(-1)
    return float(worst.mean())
