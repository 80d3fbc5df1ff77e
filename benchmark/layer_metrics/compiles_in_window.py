"""Backend compiles inside the measured window (``jax.monitoring``); must be 0."""

LAYER, UNIT, BETTER, MOVES = "runtime", "count", "lower", "samples_per_s"


def compute(ctx):
    return ctx.compiles_in_window
