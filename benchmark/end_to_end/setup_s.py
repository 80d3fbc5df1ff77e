"""Process start to the end of the drained first dispatch: imports, build,
traffic generation, warm-up (compilation, in a run that compiles) and the
first steps.  Every launch pays it."""

UNIT, BETTER = "s", "lower"


def compute(ctx):
    return ctx.setup_s
