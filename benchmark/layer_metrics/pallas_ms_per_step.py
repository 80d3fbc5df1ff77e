"""Device time per step inside Mosaic custom calls.  From the profiler trace."""

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return 1e3 * ctx.trace["custom_call_s"] / ctx.trace["steps"]
