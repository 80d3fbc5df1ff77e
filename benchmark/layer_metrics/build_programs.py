"""Programs jax compiled, or read from the compile cache, before the first
``warmup`` began: the ``backend`` stage among ``build_compile_s``'s events.
State built leaf by leaf shows here as hundreds."""

from benchmark.layer_metrics import build_compile_s

LAYER, UNIT, BETTER, MOVES = "build", "count", "lower", "setup_s"


def compute(ctx):
    found = build_compile_s.build_compiles(ctx.events)
    if found is None:
        return None
    return sum(e["stage"] == "backend" for e in found)
