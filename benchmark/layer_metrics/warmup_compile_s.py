"""The last stage of ``pipe.warmup``: the backend compile or, on a warm launch,
the compile cache's read and deserialisation of the executable.  The sum of
``compile_s`` over the program's telemetry ``warmup`` events
(``apex_tpu.cache.warmup``, one a program)."""

from benchmark.layer_metrics import warmup_trace_s

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def compute(ctx):
    return warmup_trace_s.stage_sum(ctx.events, "compile_s")
