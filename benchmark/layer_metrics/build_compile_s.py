"""How much of ``build_s`` is jax lowering and compiling (or reading from the
compile cache) the init and traffic programs: the summed ``dur`` of the
program's telemetry ``compile`` events, both stages, that end before the first
``warmup`` event begins."""

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def build_compiles(events):
    """The ``compile`` events that end before the first ``warmup`` begins, or
    None where the stream lacks either kind."""
    warmups = [e for e in events if e["kind"] == "warmup"]
    compiles = [e for e in events if e["kind"] == "compile"]
    if not warmups or not compiles:
        return None
    begins = warmups[0]["t"] - warmups[0]["dur"]
    return [e for e in compiles if e["t"] <= begins]


def compute(ctx):
    found = build_compiles(ctx.events)
    return None if found is None else sum(e["dur"] for e in found)
