"""Device time per step of the global layer's attention (all earlier keys,
no positions): every instruction issued under ``apex.attn.full``, forward,
recomputed and ``flash_bwd`` (``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.attn.full")
