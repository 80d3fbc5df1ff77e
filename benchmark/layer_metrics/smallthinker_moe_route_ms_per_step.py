"""Device time per step of the routing: every instruction issued under
``apex.moe.route`` (float32 logits of the attention's normed input at full
precision, top-6 of 64, the softmax over the six, load counts, the sort of
the pairs by expert held), forward, backward and recomputed
(``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.moe.route")
