"""The held experts' share of their roofline: the least time the chip could
take for the grouped products of a step over the time it took under
``apex.moe.experts`` (``moe_experts_ms_per_step``, which includes the
recomputed forward, the gather and the activation, so the share is of the work
that counts).  The least time is the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, both from ``benchmark/lfm2_flops.py`` at the rows the
program itself counted for the experts held (the step metric ``moe_load``,
mean over the steps the trace holds: ``lfm2_flops.traced``)."""

from benchmark import lfm2_flops, scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "%", "higher", "samples_per_s"


def compute(ctx):
    ms = scope_reduce.ms_per_step(ctx, "apex.moe.experts")
    cfg = lfm2_flops.cell_config(ctx.workload)
    counts = lfm2_flops.load_counts(ctx.step_metrics, cfg)
    if not ms or counts is None:
        return None
    rows = float(lfm2_flops.traced(
        ctx, lfm2_flops.held_rows(cfg, counts)).mean())
    least_s = max(
        lfm2_flops.moe_experts_train_flops(cfg, rows)
        / ctx.peaks["bf16_flops_per_s"],
        lfm2_flops.moe_experts_train_bytes(cfg, rows)
        / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100 * least_s / (ms * 1e-3)
