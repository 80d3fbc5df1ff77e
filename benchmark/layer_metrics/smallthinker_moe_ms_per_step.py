"""Device time per step of the expert layers: every instruction issued under
``apex.moe`` (the softmax router, top-6, the sort of the pairs by expert
held, the row gathers, the grouped products, ReGLU, the weighted sum back),
forward, backward and recomputed, without the events that wrap a loop
(``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.moe")
