"""Peak device memory on the fullest chip, as the device's allocator counts it
(``memory_stats()["peak_bytes_in_use"]``), read after the window and before
the reference check.  Users size batches to it."""

UNIT, BETTER = "GiB", "lower"


def compute(ctx):
    return ctx.peak_bytes / 2 ** 30
