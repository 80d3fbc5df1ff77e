"""The lowering stage of ``pipe.warmup``: jaxpr to StableHLO, the Mosaic
kernels lowered inside it, which no compile cache skips.  The sum of
``lower_s`` over the program's telemetry ``warmup`` events
(``apex_tpu.cache.warmup``, one a program)."""

from benchmark.layer_metrics import warmup_trace_s

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def compute(ctx):
    return warmup_trace_s.stage_sum(ctx.events, "lower_s")
