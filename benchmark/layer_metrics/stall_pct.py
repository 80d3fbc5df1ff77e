"""Share of the window lost to dispatches slower than the median one: what
``samples_per_s``, a median, does not see (a stall of the machine, a pause
of the host, a periodic slow step).  Dispatches the tracer held up are left
out."""

import statistics

LAYER, UNIT, BETTER, MOVES = "runtime", "%", "lower", "samples_per_s"


def compute(ctx):
    steady = statistics.median(ctx.intervals) * len(ctx.intervals)
    return 100 * (1 - steady / sum(ctx.intervals))
