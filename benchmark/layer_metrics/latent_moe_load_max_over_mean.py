"""How unevenly the router loads the latent experts held: the fullest held
expert's rows over the mean of the held experts' rows, in the worst expert
layer of a step, mean over the window's steps.  1 is an even load.  From the
program's own counter (the step metric ``moe_load``, which the family lifts
out of the model's state)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ratio", "lower", "samples_per_s"


def compute(ctx):
    if "moe_load" not in ctx.step_metrics:
        return None
    cfg = nemotron_flops.cell_config(ctx.workload)
    held = nemotron_flops.held(cfg, nemotron_flops.load_counts(
        ctx.step_metrics, cfg)).astype(float)
    worst = (held.max(-1) / held.mean(-1).clip(min=1e-30)).max(-1)
    return float(worst.mean())
