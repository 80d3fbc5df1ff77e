"""Images or tokens trained per second over all the cell's chips (the
configuration's file says which a sample is), in the steady state: the
samples of one dispatch over the median time from one dispatch's completion
to the next, over the whole window.

A median and not the window's total, because one-chip machines stall for a
second or two now and then (3 of 33 runs, PR 22): such a stall moves the
total by 5-10% and the median not at all.  What the median does not see is
reported per layer as ``stall_pct``."""

import statistics

UNIT, BETTER = "samples/s", "higher"


def compute(ctx):
    return ctx.k * ctx.samples_per_step / statistics.median(ctx.intervals)
