"""Programs of the run, up to the end of the window, that the persistent
compile cache did not hold: the program's telemetry ``compile`` events with
``cache`` ``miss``.  0 on a warm launch; a program whose cache key is unstable
shows here before it shows as tens of seconds of ``setup_s``."""

LAYER, UNIT, BETTER, MOVES = "build", "count", "lower", "setup_s"


def compute(ctx):
    caches = [e.get("cache") for e in ctx.events if e["kind"] == "compile"]
    return caches.count("miss") if caches else None
