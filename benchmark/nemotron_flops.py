"""Model FLOPs and the held latent experts' own work for the ``nemotron_h``
family, in closed form from the configuration's keys.

Counted as ``flops.py`` counts: a multiply and an add are two operations,
only matrix work counts (the depthwise taps, norms, gates, squares and the
embedding look-up do not), causal attention is half of ``seq x seq``, the
scan in its chunked form at the published chunk with the causal half of the
in-chunk products, backward is twice the forward, recomputed operations never
count.  The experts are counted by the rows they are sent: in the model's
closed form the expected share of a uniform router (``tokens x k x held /
routed``), in the roofline by the rows the program counted.  These functions
count the same work whatever implements the grouped products, so a share
computed from them cannot pass 100% and does not move when the
implementation does.

Also here, for the readers of the latent layer's metrics: the program's load
counter as an array, and the scopes' device time without the events that
wrap a loop (``scope_ms``, which also writes ``benchmark/out/<cell>/
scopes.json`` as ``scope_reduce`` does, the wrappers left out).
"""

import collections
import glob
import os

import numpy as np

from benchmark import phase_reduce, scope_reduce, trace_reduce
from benchmark.lfm2_flops import cell_config, traced      # noqa: F401

#: ``XLA Ops`` events that span the events of a computation they call: the
#: body's instructions are events of their own, under their own scopes
_WRAPPERS = ("while", "conditional", "call")
_memo = {}      # path of a trace -> (ns by op_name, steps of each chip)


def _routed(cfg):
    """The router's width: the published number of experts."""
    return cfg["published"]["n_routed_experts"]


def _count(cfg, letter):
    return cfg["hybrid_override_pattern"].count(letter)


def load_counts(step_metrics, cfg):
    """The step metric ``moe_load`` (the rows each of the router's experts
    was sent, per expert layer and step) as ``[steps, expert layers, routed
    experts]``; ``None`` where the program reports none."""
    flat = step_metrics.get("moe_load")
    if flat is None:
        return None
    return np.asarray(flat).reshape(-1, _count(cfg, "E"), _routed(cfg))


def held(cfg, load):
    """The counts of the experts held, of ``load``: ``[..., routed experts]``."""
    first = cfg["expert_offset"]
    return load[..., first:first + cfg["n_routed_experts"]]


def held_rows(cfg, load):
    """Rows sent to the experts held, summed over the expert layers, for
    ``load``: ``[..., expert layers, routed experts]`` counts."""
    return held(cfg, load).sum((-1, -2))


def expert_flops_per_row(cfg):
    """Forward FLOPs of one (token, slot) pair through its expert: two
    products of ``latent x moe_intermediate``."""
    return 2 * 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def latent_experts_train_flops(cfg, rows):
    """Forward plus backward of the grouped products over ``rows`` pairs (all
    expert layers of a step together), counted once."""
    return 3 * expert_flops_per_row(cfg) * rows


def latent_experts_train_bytes(cfg, rows, itemsize=2):
    """Bytes the held experts have to move in a step whatever implements
    them: forward one read of the latent rows and of the held experts'
    weights and one write of the result; backward those again (the rows, the
    weights, the rows' gradient written) plus one read of the output's
    gradient and one write of the weights' gradient."""
    per_row = cfg["moe_latent_size"] * itemsize
    weights = (_count(cfg, "E") * cfg["n_routed_experts"] * 2
               * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
               * itemsize)
    return 5 * per_row * rows + 3 * weights


def ssd_forward_flops(cfg):
    """Forward FLOPs of the scan for one token of one Mamba-2 layer, at the
    heads and groups held."""
    q, n = cfg["chunk_size"], cfg["ssm_state_size"]
    hp = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return (2 * (q // 2) * n * cfg["n_groups"]  # C B^T, the causal half
            + 2 * (q // 2) * hp                 # the masked product with x
            + 2 * n * hp                        # the state a chunk leaves
            + 2 * n * hp)                       # reading the entering state


def forward(cfg, seq):
    """Forward FLOPs of one sequence of ``seq`` tokens at what is held: the
    mixers' projections and scan, causal attention, the router, the latent
    projections, the shared expert, the held experts at their expected rows
    and the untied head."""
    d = cfg["hidden_size"]
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    share = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / _routed(cfg)
    per_token = {
        "M": (2 * (d * (2 * d_inner + bc + cfg["mamba_num_heads"])
                   + d_inner * d) + ssd_forward_flops(cfg)),
        "*": (2 * (2 * d * q_width + 2 * d * kv_width)
              + 2 * seq * q_width),             # QK^T and PV, halved
        "E": (2 * d * _routed(cfg) + 2 * 2 * d * cfg["moe_latent_size"]
              + 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"]
              * cfg["n_shared_experts"]
              + share * expert_flops_per_row(cfg)),
    }
    return seq * (sum(per_token[kind]
                      for kind in cfg["hybrid_override_pattern"])
                  + 2 * d * cfg["vocab_size"])


def train(cfg, batch, seq):
    return 3 * batch * forward(cfg, seq)


def _by_op_name(path, k, hlo):
    """``scope_reduce._by_op_name`` without the events that wrap a loop or a
    branch: nanoseconds of the device events inside whole executions of the
    step program, summed by the ``op_name`` of the instruction that ran."""
    from jax.profiler import ProfileData

    issued_under = {}
    for name, rest in phase_reduce._INSTRUCTION.findall(hlo):
        m = phase_reduce._OP_NAME.search(rest)
        issued_under[name] = m.group(1) if m else ""
    took, steps = collections.Counter(), []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if not (trace_reduce._DEVICE.match(plane.name)
                and "XLA Modules" in lines and "XLA Ops" in lines):
            continue
        runs = phase_reduce._step_runs(lines)
        steps.append((len(runs) - 2) * k)
        events = ((ev.start_ns, ev.end_ns, ev.name)
                  for ev in lines["XLA Ops"].events)
        for op_name, name, ns in phase_reduce._inside_runs(events, runs,
                                                           issued_under):
            if trace_reduce.describe(name)[1].split("/")[0] not in _WRAPPERS:
                took[op_name] += ns
    return took, steps


def scope_ms(ctx, *scopes):
    """Milliseconds per step, averaged over the chips, of the device
    operations whose ``op_name`` contains one of ``scopes``, the loops'
    wrapper events left out; ``None`` where no instruction of the step was
    issued under any of them, or there is no trace."""
    if not getattr(ctx, "hlo", None):
        return None
    found = glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "out", ctx.workload,
        "trace", "**", "*.xplane.pb"), recursive=True)
    if not found:
        return None
    path = sorted(found)[-1]
    if path not in _memo:
        _memo[path] = _by_op_name(path, ctx.k, ctx.hlo)
        # for PERF.md's breakdown: ms per step by innermost apex.* scope
        scope_reduce._write_by_scope(
            os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                         ctx.workload), *_memo[path])
    took, steps = _memo[path]
    under = [ns for op_name, ns in took.items()
             if any(scope in op_name for scope in scopes)]
    if not under or not steps or min(steps) < 1:
        return None
    return sum(under) * 1e-6 / (len(steps) * min(steps))
