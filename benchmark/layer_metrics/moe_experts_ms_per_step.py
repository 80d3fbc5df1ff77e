"""Device time per step of the held experts proper: every instruction issued
under ``apex.moe.experts`` (the gather of the sorted rows, the three grouped
products, the activation), forward, backward and recomputed.  The profiler
trace joined with the model's scopes (``scope_reduce``)."""

from benchmark import scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return scope_reduce.ms_per_step(ctx, "apex.moe.experts")
