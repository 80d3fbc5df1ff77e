"""Device time per step of the routed-expert layers: every instruction issued
under ``apex.moe`` (router scores, top-k, sort, gathers, grouped products,
activation, the weighted sum back), forward, backward and recomputed.  The
profiler trace joined with the model's scopes (``scope_reduce``)."""

from benchmark import scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return scope_reduce.ms_per_step(ctx, "apex.moe")
