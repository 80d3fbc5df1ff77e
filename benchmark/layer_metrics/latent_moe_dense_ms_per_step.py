"""Device time per step of the latent layers' dense parts: every instruction
issued under ``apex.moe.latent`` (the projections into and out of the latent
space) or ``apex.moe.shared`` (the shared expert on the hidden state),
forward, backward and recomputed (``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.moe.latent",
                                   "apex.moe.shared")
