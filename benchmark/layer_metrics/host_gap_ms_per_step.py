"""Device time per step lost between two executions of the step program:
what the host's dispatch, fetch and glue fail to hide.  From the profiler
trace.  (The program's telemetry ``gap`` is not this: with the fetch one
dispatch behind it is mostly the wait for the previous step.)"""

LAYER, UNIT, BETTER, MOVES = "runtime", "ms", "lower", "samples_per_s"


def compute(ctx):
    return 1e3 * ctx.trace["between_programs_s"] / ctx.trace["steps"]
