"""Device time of one step: the union of the device's operation intervals over
the traced steps, averaged over the chips.  From the profiler trace."""

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return 1e3 * ctx.trace["busy_s"] / ctx.trace["steps"]
