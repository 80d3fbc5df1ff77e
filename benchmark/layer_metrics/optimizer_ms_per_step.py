"""Device time of ``optimizer.update`` per step: the operations issued under
``apex.optimizer``.
The profiler trace joined with the program's scopes (``phase_reduce``)."""

from benchmark import phase_reduce

LAYER, UNIT, BETTER, MOVES = "train_step", "ms", "lower", "samples_per_s"


def compute(ctx):
    return phase_reduce.ms_per_step(ctx, "optimizer")
