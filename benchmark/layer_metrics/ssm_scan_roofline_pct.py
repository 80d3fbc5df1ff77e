"""The scan's share of its roofline: the least time the chip could take for
the scan's work of a step over the time it took (``ssm_scan_ms_per_step``,
which includes the recomputed forward, so the share is of the work that
counts).  The least time is the larger of FLOPs over the bf16 peak and bytes
over the HBM peak, both from ``benchmark/hybrid_flops.py``: forward plus
backward of the chunked form, counted once."""

import json
import os

from benchmark import hybrid_flops, scope_reduce

LAYER, UNIT, BETTER, MOVES = "kernels", "%", "higher", "samples_per_s"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(workload):
    with open(os.path.join(_ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    cell = next(c for c in manifest["workloads"] if c["name"] == workload)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(_ROOT, entry["file"]), encoding="utf-8") as f:
        return json.load(f)


def compute(ctx):
    ms = scope_reduce.ms_per_step(ctx, "apex.ssm.scan")
    if not ms:
        return None
    cfg = _config(ctx.workload)
    least_s = max(
        hybrid_flops.ssd_train_flops(cfg, ctx.samples_per_step)
        / ctx.peaks["bf16_flops_per_s"],
        hybrid_flops.ssd_train_bytes(cfg, ctx.samples_per_step)
        / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100 * least_s / (ms * 1e-3)
