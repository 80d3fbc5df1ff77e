"""Device time of the backward pass per step: the operations under
``transpose(jvp(apex.forward))``, rematerialised forward included.
The profiler trace joined with the program's scopes (``phase_reduce``)."""

from benchmark import phase_reduce

LAYER, UNIT, BETTER, MOVES = "train_step", "ms", "lower", "samples_per_s"


def compute(ctx):
    return phase_reduce.ms_per_step(ctx, "backward")
