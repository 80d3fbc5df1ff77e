"""The family adapter's ``build``: model, initial state, pipeline and traffic."""

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def compute(ctx):
    return ctx.spans["build"]
