"""Mosaic custom calls in the compiled step (the count PR 21 found step time
to track)."""

LAYER, UNIT, BETTER, MOVES = "kernels", "count", "lower", "samples_per_s"


def compute(ctx):
    return ctx.hlo.count('custom_call_target="tpu_custom_call"')
