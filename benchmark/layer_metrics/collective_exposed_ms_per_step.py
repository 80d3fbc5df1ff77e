"""The part of the collectives' time during which nothing else ran on that
device, per step.  From the profiler trace."""

LAYER, UNIT, BETTER, MOVES = "parallel", "ms", "lower", "samples_per_s"


def compute(ctx):
    if not ctx.trace["collectives"]:
        return None
    return 1e3 * ctx.trace["collective_exposed_s"] / ctx.trace["steps"]
