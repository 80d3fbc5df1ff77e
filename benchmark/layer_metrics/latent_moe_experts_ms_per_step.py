"""Device time per step of the held latent experts proper: every instruction
issued under ``apex.moe.experts`` (the gather of a wave's latent rows, the
two grouped products, ``relu ** 2``, and their backward), forward, backward
and recomputed, without the events that wrap a loop
(``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.moe.experts")
