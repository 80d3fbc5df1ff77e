"""Steps of the window the loss scaler skipped: a skipped step trains nothing."""

LAYER, UNIT, BETTER, MOVES = "train_step", "count", "lower", "samples_per_s"


def compute(ctx):
    return int(ctx.step_metrics["overflow"].sum())
