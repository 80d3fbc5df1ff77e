"""Device time per step that no phase claims: ``apex.metrics``, and every
operation whose metadata names no scope of ``make_train_step``.
The profiler trace joined with the program's scopes (``phase_reduce``)."""

from benchmark import phase_reduce

LAYER, UNIT, BETTER, MOVES = "train_step", "ms", "lower", "samples_per_s"


def compute(ctx):
    return phase_reduce.ms_per_step(ctx, "other")
