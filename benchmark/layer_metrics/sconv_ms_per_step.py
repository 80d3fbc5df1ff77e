"""Device time per step of the gated short convolutions: every instruction
issued under ``apex.sconv`` (``in_proj``, the gates and the three taps,
``out_proj``), forward, backward and recomputed.  The profiler trace joined
with the model's scopes (``scope_reduce``)."""

from benchmark import scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "ms", "lower", "samples_per_s"


def compute(ctx):
    return scope_reduce.ms_per_step(ctx, "apex.sconv")
