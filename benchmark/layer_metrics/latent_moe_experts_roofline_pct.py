"""The held latent experts' share of their roofline: the least time the chip
could take for the grouped products of a step over the time it took under
``apex.moe.experts`` (``latent_moe_experts_ms_per_step``, which includes the
forward recomputed twice, the gathers and the activation, so the share is of
the work that counts).  The least time is the larger of FLOPs over the bf16
peak and bytes over the HBM peak, both from ``benchmark/nemotron_flops.py``
at the rows the program itself counted for the experts held (the step metric
``moe_load``, mean over the steps the trace holds)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "%", "higher", "samples_per_s"


def compute(ctx):
    ms = nemotron_flops.scope_ms(ctx, "apex.moe.experts")
    if not ms or "moe_load" not in ctx.step_metrics:
        return None
    cfg = nemotron_flops.cell_config(ctx.workload)
    rows = float(nemotron_flops.traced(ctx, nemotron_flops.held_rows(
        cfg, nemotron_flops.load_counts(ctx.step_metrics, cfg))).mean())
    least_s = max(
        nemotron_flops.latent_experts_train_flops(cfg, rows)
        / ctx.peaks["bf16_flops_per_s"],
        nemotron_flops.latent_experts_train_bytes(cfg, rows)
        / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100 * least_s / (ms * 1e-3)
