"""Collective instructions of every kind in the compiled step; an
asynchronous start/done pair counts once."""

import re

LAYER, UNIT, BETTER, MOVES = "parallel", "count", "lower", "samples_per_s"

_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)(-start)?\(")


def compute(ctx):
    return len(_COLLECTIVE.findall(ctx.hlo)) or None
