"""Model FLOPs and the held experts' own work for the LFM2-MoE family, in
closed form from the configuration's keys.

Counted as ``flops.py`` counts: a multiply and an add are two operations,
only matrix work counts (the depthwise taps, norms, gates, rotations and the
embedding look-up do not), causal attention is half of ``seq x seq``,
backward is twice the forward, recomputed operations never count.  The
experts are counted by the rows they are sent: in the model's closed form the
expected share of a uniform router (``tokens x k x held / routed``), in the
roofline by the rows the program counted.  These functions count the same
work whatever implements the grouped matmul, so a share computed from them
cannot pass 100% and does not move when the implementation does.

Also here, for the readers of the expert layer's metrics: the cell's
configuration by the cell's name, the program's load counter as an array, and
which of a window's steps the trace holds.
"""

import json
import os

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cell_config(workload):
    """The configuration file of the cell ``workload``."""
    with open(os.path.join(_ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    cell = next(c for c in manifest["workloads"] if c["name"] == workload)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(_ROOT, entry["file"]), encoding="utf-8") as f:
        return json.load(f)


def load_counts(step_metrics, cfg):
    """The step metric ``moe_load`` (the rows each of the router's experts
    was sent, per expert layer and step) as ``[steps, expert layers, routed
    experts]``; ``None`` where the program reports none."""
    flat = step_metrics.get("moe_load")
    if flat is None:
        return None
    return np.asarray(flat).reshape(-1, _expert_layers(cfg), _routed(cfg))


def traced(ctx, per_step):
    """The entries of ``per_step`` (one a step of the window, in order) that
    belong to the steps the trace holds whole, so that rows and device time
    are of the same steps: the held experts' rows drift while the window
    trains on its one batch.  ``run.py`` starts the trace after
    ``_TRACE_AFTER_DISPATCHES`` dispatches with the last of them on the
    device, so the first whole execution is the next one.  All of
    ``per_step`` where there is no trace."""
    trace = getattr(ctx, "trace", None)
    if not trace:
        return per_step
    from benchmark import run
    first = run._TRACE_AFTER_DISPATCHES * ctx.k
    held = per_step[first:first + int(trace["steps"])]
    return held if len(held) else per_step


def _routed(cfg):
    """The router's width: the published number of experts."""
    return cfg["published"]["num_experts"]


def _expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def expert_flops_per_row(cfg):
    """Forward FLOPs of one (token, slot) pair through its expert: three
    products of ``hidden x moe_intermediate``."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_experts_train_flops(cfg, rows):
    """Forward plus backward of the grouped products over ``rows`` pairs (all
    expert layers of a step together), counted once."""
    return 3 * expert_flops_per_row(cfg) * rows


def moe_experts_train_bytes(cfg, rows, itemsize=2):
    """Bytes the held experts have to move in a step whatever implements
    them: forward one read of the rows and of the held experts' weights and
    one write of the result; backward those again (the rows, the weights, the
    rows' gradient written) plus one read of the output's gradient and one
    write of the weights' gradient."""
    per_row = cfg["hidden_size"] * itemsize
    weights = (_expert_layers(cfg) * cfg["num_experts"] * 3
               * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize)
    return 5 * per_row * rows + 3 * weights


def held(cfg, load):
    """The counts of the experts held, of ``load``: ``[..., routed experts]``."""
    first = cfg["expert_offset"]
    return load[..., first:first + cfg["num_experts"]]


def held_rows(cfg, load):
    """Rows sent to the experts held, summed over the expert layers, for
    ``load``: ``[..., expert layers, routed experts]`` counts."""
    return held(cfg, load).sum((-1, -2))


def forward(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens: the mixers'
    projections, causal attention, the dense MLP, the router, the held
    experts at their expected rows and the tied head."""
    d = cfg["hidden_size"]
    head = d // cfg["num_attention_heads"]
    operator = {
        "conv": 2 * (3 * d * d + d * d),
        "full_attention": (2 * (2 * d * d
                                + 2 * d * head * cfg["num_key_value_heads"])
                           + 2 * seq * d),      # QK^T and PV, halved
    }
    dense = 2 * 3 * d * cfg["intermediate_size"]
    share = cfg["num_experts_per_tok"] * cfg["num_experts"] / _routed(cfg)
    routed = 2 * d * _routed(cfg) + share * expert_flops_per_row(cfg)
    per_token = sum(
        operator[kind] + (dense if i < cfg["num_dense_layers"] else routed)
        for i, kind in enumerate(cfg["layer_types"]))
    return seq * (per_token + 2 * d * cfg["vocab_size"])


def train(cfg, batch, seq):
    return 3 * batch * forward(cfg, seq)
