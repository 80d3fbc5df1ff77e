"""Share of the traced window in which no operation ran on the device,
averaged over the chips.  From the profiler trace, never from a host clock."""

LAYER, UNIT, BETTER, MOVES = "device", "%", "lower", "samples_per_s"


def compute(ctx):
    return 100 * (1 - ctx.trace["busy_s"] / ctx.trace["window_s"])
