"""Model FLOP/s utilization end to end: model FLOPs of the traced steps over
the traced wall time at the published bf16 peak.  Throughput times a
constant; named apart from ``busy_mfu_pct``."""

LAYER, UNIT, BETTER, MOVES = "device", "%", "higher", "samples_per_s"


def compute(ctx):
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return (100 * ctx.flops_per_step * ctx.trace["steps"]
            / (ctx.trace["window_s"] * peak))
