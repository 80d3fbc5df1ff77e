"""Time per step with a collective in flight, from the start of each to its
end (for an asynchronous one, from its start operation to the end of its
done operation), overlaps merged.  From the profiler trace."""

LAYER, UNIT, BETTER, MOVES = "parallel", "ms", "lower", "samples_per_s"


def compute(ctx):
    if not ctx.trace["collectives"]:
        return None
    return 1e3 * ctx.trace["collective_s"] / ctx.trace["steps"]
