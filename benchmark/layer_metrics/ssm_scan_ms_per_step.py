"""Device time per step of the state-space scan proper: every instruction
issued under ``apex.ssm.scan`` (decays, in-chunk products, chunk states and
their recurrence), forward, backward and recomputed.  The profiler trace
joined with the model's scopes (``scope_reduce``)."""

from benchmark import scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return scope_reduce.ms_per_step(ctx, "apex.ssm.scan")
