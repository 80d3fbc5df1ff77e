"""Device time per step of the latent expert layers: every instruction issued
under ``apex.moe`` (router scores, top-k, sort, the latent projections, the
waves of the expert chain, the weighted sum back, the shared expert),
forward, backward and recomputed, without the events that wrap a loop.  The
profiler trace joined with the model's scopes (``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.moe")
