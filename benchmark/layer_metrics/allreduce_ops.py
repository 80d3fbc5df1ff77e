"""All-reduce instructions in the compiled step (gradients; XLA combines
them, so the count is small); an asynchronous pair counts once."""

LAYER, UNIT, BETTER, MOVES = "parallel", "count", "lower", "samples_per_s"


def compute(ctx):
    return (ctx.hlo.count(" all-reduce(") + ctx.hlo.count(" all-reduce-start(")
            ) or None
