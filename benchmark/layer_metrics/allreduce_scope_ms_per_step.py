"""Device time per step of what ``reduce_gradients`` issues (``apex.allreduce``):
the collectives' start and done operations and the converts and copies made
for them, which ``collective_exposed_ms_per_step`` leaves out.
The profiler trace joined with the program's scopes (``phase_reduce``)."""

from benchmark import phase_reduce

LAYER, UNIT, BETTER, MOVES = "parallel", "ms", "lower", "samples_per_s"


def compute(ctx):
    return phase_reduce.ms_per_step(ctx, "allreduce")
