"""The window layers' attention's share of its roofline: the least time the
chip could take for the attention core of the three window layers of a step
(``QK^T`` and ``PV`` over the visible pairs, ``0 <= i - j < window``, forward
and backward, counted once) over the time it took under
``apex.attn.window`` (``smallthinker_window_attn_ms_per_step``: the forward
kernel, its recomputation, ``flash_bwd`` and the layout passes, so the share
is of the work that counts).  The least time is the larger of FLOPs over the
bf16 peak and bytes over the HBM peak, both from
``benchmark/smallthinker_flops.py``."""

from benchmark import nemotron_flops, smallthinker_flops

LAYER, UNIT, BETTER, MOVES = "kernels", "%", "higher", "samples_per_s"


def compute(ctx):
    ms = nemotron_flops.scope_ms(ctx, "apex.attn.window")
    if not ms:
        return None
    cfg = smallthinker_flops.cell_config(ctx.workload)
    seq = ctx.samples_per_step // ctx.chips
    least_s = max(
        smallthinker_flops.window_attention_train_flops(cfg, seq)
        / ctx.peaks["bf16_flops_per_s"],
        smallthinker_flops.window_attention_train_bytes(cfg, seq)
        / ctx.peaks["hbm_bytes_per_s"])
    return 100 * least_s / (ms * 1e-3)
