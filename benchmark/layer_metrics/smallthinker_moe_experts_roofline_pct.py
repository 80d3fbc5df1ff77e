"""The held experts' share of their roofline: the least time the chip could
take for the grouped products of a step over the time it took under
``apex.moe.experts`` (which includes the recomputed forward, the row gather
and ReGLU, so the share is of the work that counts), without the events that
wrap a loop (``nemotron_flops.scope_ms``).  The least time is the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, both from
``benchmark/smallthinker_flops.py`` at the rows the program itself counted
for the experts held (the step metric ``moe_load``, mean over the steps the
trace holds)."""

from benchmark import nemotron_flops, smallthinker_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "%", "higher", "samples_per_s"


def compute(ctx):
    ms = nemotron_flops.scope_ms(ctx, "apex.moe.experts")
    cfg = smallthinker_flops.cell_config(ctx.workload)
    counts = smallthinker_flops.load_counts(ctx.step_metrics, cfg)
    if not ms or counts is None:
        return None
    rows = float(smallthinker_flops.traced(
        ctx, smallthinker_flops.held_rows(cfg, counts)).mean())
    least_s = max(
        smallthinker_flops.experts_train_flops(cfg, rows)
        / ctx.peaks["bf16_flops_per_s"],
        smallthinker_flops.experts_train_bytes(cfg, rows)
        / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100 * least_s / (ms * 1e-3)
