"""Device time per step of the latent layers' routing: every instruction
issued under ``apex.moe.route`` (float32 scores at full precision, sigmoid,
top-22 of 512, weights, load counts, the sort of the pairs by expert held),
forward, backward and recomputed (``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.moe.route")
