"""The trace stage of ``pipe.warmup``: Python to jaxpr, the step function run
over abstract arguments, with whatever small programs that compiles on the
way.  The sum of ``trace_s`` over the program's telemetry ``warmup`` events
(``apex_tpu.cache.warmup``, one a program)."""

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def stage_sum(events, field):
    """``field`` summed over the ``warmup`` events, or None where the stream
    has none."""
    stages = [e[field] for e in events if e["kind"] == "warmup"]
    return sum(stages) if stages else None


def compute(ctx):
    return stage_sum(ctx.events, "trace_s")
