"""Device time per step of the window layers' attention: every instruction
issued under ``apex.attn.window`` (the flash kernel over the bounded grid of
the 4,096-key band, its recomputation in the backward pass, ``flash_bwd``,
and the layout passes around them), without the events that wrap a loop.
The profiler trace joined with the model's scopes
(``nemotron_flops.scope_ms``)."""

from benchmark import nemotron_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return nemotron_flops.scope_ms(ctx, "apex.attn.window")
