"""``pipe.warmup``: tracing, lowering (Mosaic kernels included) and the backend
compile or its cache hit."""

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def compute(ctx):
    return ctx.spans["warmup"]
