"""Model FLOPs of a step over what the chips could do at the published bf16
peak in the time the device was busy with it: utilization while working,
idle time left out."""

LAYER, UNIT, BETTER, MOVES = "kernels", "%", "higher", "samples_per_s"


def compute(ctx):
    busy_per_step = ctx.trace["busy_s"] / ctx.trace["steps"]
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100 * ctx.flops_per_step / (busy_per_step * peak)
