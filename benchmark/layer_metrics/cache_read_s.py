"""What a warm launch pays to read its executables back from the persistent
compile cache (180 to 300 MB a step program): the summed ``read_s`` of the
program's telemetry ``compile`` events.  0 where nothing was a hit."""

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def compute(ctx):
    compiles = [e for e in ctx.events if e["kind"] == "compile"]
    return sum(e.get("read_s", 0.0) for e in compiles) if compiles else None
