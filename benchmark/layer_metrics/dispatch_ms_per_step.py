"""Host time inside ``StepPipeline.step_window`` per step: the median ``dur`` of
the program's telemetry ``window`` events over the steps of one dispatch."""

import statistics

LAYER, UNIT, BETTER, MOVES = "runtime", "ms", "lower", "samples_per_s"


def compute(ctx):
    durs = [e["dur"] for e in ctx.events if e["kind"] == "window"]
    return 1e3 * statistics.median(durs) / ctx.k if durs else None
