"""Expert parallelism — switch-style MoE with all_to_all dispatch.

Beyond-parity scope (the reference implements data parallelism only,
SURVEY.md §2.10).  The TPU-native expert layer: tokens and experts are
both sharded over the ``ep`` mesh axis (each rank hosts one expert and a
shard of the batch); routing dispatches tokens to their expert's rank
with one ``all_to_all``, the expert FFN runs as a dense local matmul,
and a second ``all_to_all`` returns the outputs — the classic
Switch-Transformer dataflow expressed as two ICI collectives.

Capacity semantics: each expert accepts at most
``capacity = ceil(tokens_per_rank * capacity_factor / n_experts)`` tokens
per source rank; overflowing tokens are *dropped* (contribute zero, the
standard switch behavior) and reported via the aux outputs.  The router
gate is applied on the combine side so gradients flow into the router.

Call inside ``shard_map``; one expert per ``ep`` rank (``n_experts ==
lax.axis_size(axis_name)``).

For routing to more than one expert a token without a capacity or a dropped
row, and for several experts a rank, see :mod:`apex_tpu.ops.moe`: the expert
layer that is told which experts it holds, routes over all of them and
computes its own experts' part with grouped matmuls.  It runs on one chip
without an exchange; this module's two ``all_to_all``s have not been rebuilt
on it (``ROADMAP.md``, Reach).
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax



class MoEAux(NamedTuple):
    """Routing diagnostics + the load-balancing loss term."""
    load_balance_loss: jnp.ndarray    # scalar, Switch aux loss
    dropped_fraction: jnp.ndarray     # scalar in [0, 1]


def _dispatch_indices(assign, n_experts, capacity):
    """Position of each token within its expert's capacity buckets.

    Returns ``(slot, kept)``: ``slot[t]`` = index in [0, capacity) of token
    ``t`` inside its expert bucket, ``kept[t]`` = False when the bucket was
    already full (token dropped).
    """
    onehot = jax.nn.one_hot(assign, n_experts, dtype=jnp.int32)  # [T, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot          # 1-based
    slot = jnp.sum(pos_in_expert, axis=1) - 1                    # [T]
    kept = slot < capacity
    return jnp.clip(slot, 0, capacity - 1), kept


def moe_layer(x, router_w, expert_fn: Callable, expert_params, *,
              axis_name: str, capacity_factor: float = 1.25):
    """Top-1 (switch) mixture-of-experts over the ``ep`` mesh axis.

    ``x``: ``[T, d]`` this rank's token shard.  ``router_w``: ``[d, E]``
    replicated router weights.  ``expert_fn(params, h) -> h`` applied by
    this rank to every token routed to its expert; ``expert_params`` is
    this rank's expert's parameter pytree (shard the stacked experts with
    ``P("ep")`` and squeeze, as with the pipeline's stage params).

    Returns ``(y [T, d], MoEAux)``.
    """
    n_experts = lax.axis_size(axis_name)
    if router_w.shape[-1] != n_experts:
        raise ValueError(
            f"router_w has {router_w.shape[-1]} expert columns but the "
            f"'{axis_name}' axis has {n_experts} ranks — this layer places "
            f"exactly one expert per rank")
    t_local, d = x.shape
    capacity = max(1, math.ceil(t_local * capacity_factor / n_experts))

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # [T, E]
    gate = jnp.max(probs, axis=-1)                               # [T]
    assign = jnp.argmax(probs, axis=-1)                          # [T]

    # Switch load-balancing aux loss: E * sum_e f_e * P_e, with f (expert
    # token fractions) and P (router prob means) taken over the GLOBAL
    # batch — mean-of-local-products != product-of-global-means when
    # routing skews differ across ep ranks, so pmean both before the sum.
    f = jnp.mean(jax.nn.one_hot(assign, n_experts, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    f = lax.pmean(f, axis_name)
    p = lax.pmean(p, axis_name)
    lb_loss = n_experts * jnp.sum(f * p)

    slot, kept = _dispatch_indices(assign, n_experts, capacity)

    # Scatter tokens into per-expert capacity buckets [E, C, d].
    dispatch = jnp.zeros((n_experts, capacity, d), x.dtype)
    dispatch = dispatch.at[
        jnp.where(kept, assign, 0),
        slot].add(jnp.where(kept[:, None], x, 0.0).astype(x.dtype))

    # all_to_all #1: bucket e of every source rank lands on rank e.
    # [E, C, d] -> [E_src, C, d] on the expert's rank.
    arrived = lax.all_to_all(dispatch, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)

    out = expert_fn(expert_params, arrived.reshape(-1, d))
    out = out.reshape(n_experts, capacity, d)

    # all_to_all #2: return each source rank its tokens.
    returned = lax.all_to_all(out, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)           # [E, C, d]

    # Combine: gather each kept token's output, weight by its gate.
    y = returned[jnp.where(kept, assign, 0), slot]
    y = jnp.where(kept[:, None], y, 0.0)
    y = (y.astype(jnp.float32) * gate[:, None]).astype(x.dtype)

    dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
    return y, MoEAux(load_balance_loss=lb_loss, dropped_fraction=dropped)
