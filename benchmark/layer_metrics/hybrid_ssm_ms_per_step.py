"""Device time per step of the Mamba-2 mixers in a cell whose mixers stand
alone in their layers: every instruction issued under ``apex.ssm``
(projections, convolution, scan, gated norm), forward, backward and
recomputed (``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.ssm")
