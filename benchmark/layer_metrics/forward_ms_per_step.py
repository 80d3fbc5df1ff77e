"""Device time of the forward pass per step: the operations issued under
``apex.forward`` that are not under a ``transpose(``.
The profiler trace joined with the program's scopes (``phase_reduce``)."""

from benchmark import phase_reduce

LAYER, UNIT, BETTER, MOVES = "train_step", "ms", "lower", "samples_per_s"


def compute(ctx):
    return phase_reduce.ms_per_step(ctx, "forward")
