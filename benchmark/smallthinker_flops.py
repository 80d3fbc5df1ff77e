"""Model FLOPs, and the windowed attention's and the held experts' own work,
for the ``smallthinker`` family, in closed form from the configuration's
keys.

Counted as ``flops.py`` counts: a multiply and an add are two operations,
only matrix work counts (norms, rotations, gates and the embedding look-up
do not), backward is twice the forward, recomputed operations never count.
**Attention is counted over the (query, key) pairs a query sees**: ``j <= i``
on a global layer, ``0 <= i - j < window`` on a window layer, whatever
blocks a kernel's grid visits, so the count does not move with the
implementation and a share computed from it cannot pass 100%.  The experts
are counted by the rows they are sent: in the model's closed form the
expected share of a uniform router (``tokens x k x held / routed``), in the
roofline by the rows the program counted.

Also here, for the readers of the cell's metrics: the program's load counter
as an array.  The readers join the trace through ``nemotron_flops.scope_ms``
(which leaves out the events that wrap a loop) and take the configuration
and the traced steps from ``lfm2_flops``.
"""

import numpy as np

from benchmark.lfm2_flops import cell_config, traced      # noqa: F401


def _routed(cfg):
    """The router's width: the published number of experts."""
    return cfg["published"]["moe_num_primary_experts"]


def _layers(cfg):
    return cfg["num_hidden_layers"]


def visible_pairs(seq, window=None):
    """(query, key) pairs a causal query sees over a sequence of ``seq``:
    all earlier keys and itself, or the last ``window`` of them."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def _pairs_by_layer(cfg, seq):
    return [visible_pairs(seq, cfg["sliding_window_size"] if windowed
                          else None)
            for windowed in cfg["sliding_window_layout"]]


def attention_core_flops(cfg, pairs):
    """Forward FLOPs of ``QK^T`` and ``PV`` over ``pairs`` visible pairs."""
    return 2 * 2 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]


def window_attention_train_flops(cfg, seq, batch=1):
    """Forward plus backward of the window layers' attention core, counted
    once: every window layer of a step together."""
    pairs = sum(p for p, windowed in zip(_pairs_by_layer(cfg, seq),
                                         cfg["sliding_window_layout"])
                if windowed)
    return 3 * batch * attention_core_flops(cfg, pairs)


def window_attention_train_bytes(cfg, seq, batch=1, itemsize=2):
    """Bytes the window layers' attention has to move in a step whatever
    implements it: forward one read of q, k, v and one write of the output;
    backward one read of q, k, v, the output and its gradient and one write
    of the three gradients."""
    q = batch * seq * cfg["num_attention_heads"] * cfg["head_dim"] * itemsize
    kv = batch * seq * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    windows = sum(map(bool, cfg["sliding_window_layout"]))
    return windows * ((2 * q + 2 * kv) + (3 * q + 2 * kv) + (q + 2 * kv))


def load_counts(step_metrics, cfg):
    """The step metric ``moe_load`` (the rows each of the router's experts
    was sent, per layer and step) as ``[steps, layers, routed experts]``;
    ``None`` where the program reports none."""
    flat = step_metrics.get("moe_load")
    if flat is None:
        return None
    return np.asarray(flat).reshape(-1, _layers(cfg), _routed(cfg))


def held(cfg, load):
    """The counts of the experts held, of ``load``: ``[..., routed
    experts]``."""
    first = cfg["expert_offset"]
    return load[..., first:first + cfg["moe_num_primary_experts"]]


def held_rows(cfg, load):
    """Rows sent to the experts held, summed over the layers, for ``load``:
    ``[..., layers, routed experts]`` counts."""
    return held(cfg, load).sum((-1, -2))


def expert_flops_per_row(cfg):
    """Forward FLOPs of one (token, slot) pair through its expert: three
    products of ``hidden x moe_ffn_hidden_size``."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def experts_train_flops(cfg, rows):
    """Forward plus backward of the grouped products over ``rows`` pairs (all
    layers of a step together), counted once."""
    return 3 * expert_flops_per_row(cfg) * rows


def experts_train_bytes(cfg, rows, itemsize=2):
    """Bytes the held experts have to move in a step whatever implements
    them: forward one read of the rows and of the held experts' weights and
    one write of the result; backward those again (the rows, the weights, the
    rows' gradient written) plus one read of the output's gradient and one
    write of the weights' gradient."""
    per_row = cfg["hidden_size"] * itemsize
    weights = (_layers(cfg) * cfg["moe_num_primary_experts"] * 3
               * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"] * itemsize)
    return 5 * per_row * rows + 3 * weights


def forward(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens at what is held: the
    attention's projections and its core over the visible pairs, the router,
    the held experts at their expected rows and the untied head."""
    d = cfg["hidden_size"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    share = (cfg["moe_num_active_primary_experts"]
             * cfg["moe_num_primary_experts"] / _routed(cfg))
    per_token = (2 * (2 * d * q_width + 2 * d * kv_width)
                 + 2 * d * _routed(cfg) + share * expert_flops_per_row(cfg))
    core = sum(attention_core_flops(cfg, p)
               for p in _pairs_by_layer(cfg, seq))
    return seq * (_layers(cfg) * per_token + 2 * d * cfg["vocab_size"]) + core


def train(cfg, batch, seq):
    return 3 * batch * forward(cfg, seq)
