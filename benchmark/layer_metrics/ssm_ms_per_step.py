"""Device time per step of the Mamba-2 mixers: every instruction issued under
``apex.ssm`` (projections, convolution, scan, gated norm), forward, backward
and recomputed.  The profiler trace joined with the model's scopes
(``scope_reduce``)."""

from benchmark import scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return scope_reduce.ms_per_step(ctx, "apex.ssm")
