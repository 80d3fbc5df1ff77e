"""What mixed precision itself adds to the device's step: the master-to-compute
cast with its transpose (``apex.cast``) and the unscale, overflow agreement
and scale update (``apex.scaler``).
The profiler trace joined with the program's scopes (``phase_reduce``)."""

from benchmark import phase_reduce

LAYER, UNIT, BETTER, MOVES = "train_step", "ms", "lower", "samples_per_s"


def compute(ctx):
    return phase_reduce.ms_per_step(ctx, "cast", "scaler")
