"""Seconds the process had lived when the program's telemetry stream opened:
interpreter, imports, the chip's start-up and ``cache.enable()``, which is the
part of ``setup_s`` before ``build``.  The ``run`` event's ``process_age_s``
(the process's start time in ``/proc/self/stat`` against ``CLOCK_BOOTTIME``)."""

LAYER, UNIT, BETTER, MOVES = "build", "s", "lower", "setup_s"


def compute(ctx):
    ages = [e["process_age_s"] for e in ctx.events
            if e["kind"] == "run" and "process_age_s" in e]
    return ages[0] if ages else None
