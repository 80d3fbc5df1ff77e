"""A routed-expert (mixture of experts) layer that is told which experts it
holds: sigmoid router, top-k with a selection bias, no capacity, no drops.

The layer routes over **all** ``E`` experts and computes the part of the
result that the ``G`` experts it holds give (experts ``offset .. offset + G``
of ``E``)::

    s   = sigmoid(W_g u)                        # float32, [E]
    sel = top_k(s + b)                          # b: a selection bias, not trained
    w   = s[sel] / (sum s[sel] + 1e-6) * scale  # from the unbiased scores
    out = sum_{e in sel, e held here} w_e W2_e (silu(W1_e u) * W3_e u)

With ``G == E`` that is the whole layer; with ``G < E`` it is one chip's
share under expert parallelism, and the shares of all the chips add up to the
whole.  What the absent experts would add is not computed and nothing stands
in for them or for the exchange that would bring their tokens.

There is **no capacity**: every (token, slot) pair whose expert is held is
computed, whatever the imbalance.  The pairs are sorted by expert (a stable
sort; pairs whose expert is elsewhere sort to the tail), the rows of ``u``
are gathered in that order, and the three products run as grouped matmuls
over the sorted rows (``_grouped_matmul``: a kernel that walks only the tiles
of rows that belong to a group, so the tail costs no expert FLOPs).  The
static row bound is the worst case, every pair held here.  The result goes
back as a weighted sum over each token's slots.  Both directions of both row
movements are gathers (by the sort's permutation one way, by its inverse the
other): no scatter-add on the path.

Gradients flow through the gathers, the grouped products (input and weight
gradients by group, the kernels' own backward rules) and the weights ``w``
into the router; none through the selection, and none to the bias, which is
the caller's state.

Named scopes (metadata, like ``training.PHASE_SCOPES``): ``apex.moe.route``
(scores, top-k, weights, sort, counts), ``apex.moe.experts`` (gather, grouped
products, activation), ``apex.moe.combine``; the caller puts ``apex.moe``
around the call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..normalization.fused_layer_norm import _use_pallas

__all__ = ["MOE_SCOPES", "route", "moe_layer"]

#: rows a tile of the grouped-matmul kernel: a group's edge inside a tile
#: costs the tile twice, so smaller tiles lose less to uneven groups
_ROW_TILE = 256

#: the scopes of the expert layer, outermost first
MOE_SCOPES = ("apex.moe", "apex.moe.route", "apex.moe.experts",
              "apex.moe.combine")
_ROUTE, _EXPERTS, _COMBINE = MOE_SCOPES[1:]


def route(x, w_gate, bias, *, top_k: int, norm_topk_prob: bool = True,
          scaling: float = 1.0):
    """``(sel, weights, counts)`` for tokens ``x``: ``[N, D]``.

    ``w_gate``: ``[D, E]`` and ``bias``: ``[E]``, float32.  The scores are
    float32 at full matmul precision: the fourth and fifth of 64 sigmoid
    scores differ by a few thousandths for many tokens, so a rounded score is
    another selection.  ``sel``: ``[N, top_k]`` int32, by ``scores + bias``;
    ``weights``: ``[N, top_k]`` float32, the unbiased scores of the selected
    experts, normalised over the ``top_k`` when ``norm_topk_prob``, times
    ``scaling``; ``counts``: ``[E]`` int32, the rows each expert was sent.
    The selection carries no gradient."""
    if w_gate.dtype != jnp.float32 or bias.dtype != jnp.float32:
        raise TypeError(
            f"moe.route: the router's weight arrived as {w_gate.dtype} and "
            f"the selection bias as {bias.dtype}; the scores are float32 "
            f"whatever the compute dtype (keep the router out of the amp "
            f"cast: models.lfm2_moe.keep_fp32)")
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), w_gate,
                                    precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(scores) + bias, top_k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    experts = jnp.arange(w_gate.shape[1], dtype=sel.dtype)
    counts = (sel[..., None] == experts).sum((0, 1), dtype=jnp.int32)
    return sel, weights * scaling, counts


def _grouped_matmul(rows, weights, group_sizes):
    """``rows[r] @ weights[g]`` for the rows ``r`` of group ``g``; the rows are
    sorted by group and ``group_sizes`` says where each group ends.  Rows past
    the last group are left as they are found (not zeros: the callers mask
    them).  On the TPU the grouped-matmul kernel of ``jax.experimental``
    (megablox ``gmm``, with its own backward kernels); elsewhere, and where the
    rows do not fill its row tiles, :func:`jax.lax.ragged_dot`, which the TPU
    compiler lowers to a kernel of its own with 512-row tiles.  At 16 groups
    of about 1,024 uneven rows of 2,048 x 1,536 the first is ahead by a fifth
    (``PERF.md``, PR 31), and it keeps the caller's scopes in its metadata,
    which the compiler's kernel does not."""
    if _use_pallas() and rows.shape[0] % _ROW_TILE == 0:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        return megablox.gmm(rows, weights, group_sizes, rows.dtype,
                            (_ROW_TILE, 2048, 512))
    return jax.lax.ragged_dot(rows, weights, group_sizes)


def _from_slots(rows, pos, held, weights=None):
    """``sum_s weights[n, s] rows[pos[n, s]]`` over the slots that are held,
    in float32: one gather of ``[N, D]`` a slot.  (One gather of all ``N k``
    pairs would be re-tiled on its way to ``[N, k, D]``, a copy of its own.)
    A pair that is not held has its row in the tail, which no group wrote."""
    total = 0
    for slot in range(held.shape[1]):
        picked = jnp.where(held[:, slot, None], rows[pos[:, slot]], 0
                           ).astype(jnp.float32)
        total = total + (picked if weights is None
                         else picked * weights[:, slot, None])
    return total


@jax.custom_vjp
def _sorted_rows(x, order, pos, held):
    """Row ``r`` of the result is the token of pair ``order[r]``: ``x[order //
    k]`` for ``x``: ``[N, D]`` and ``N k`` pairs.  Backward: the inverse
    permutation, as gathers, summed over a token's slots."""
    return x[order // held.shape[1]]


def _sorted_rows_fwd(x, order, pos, held):
    return _sorted_rows(x, order, pos, held), (pos, held)


def _sorted_rows_bwd(res, g):
    pos, held = res
    return _from_slots(g, pos, held).astype(g.dtype), None, None, None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


@jax.custom_vjp
def _combine(rows, weights, order, pos, held):
    """``y[n] = sum_s weights[n, s] rows[pos[n, s]]`` over the slots that are
    held, float32 sums rounded once.  Backward, in the sorted order: one
    gather of ``dy`` by ``order`` serves ``d rows[r] = weights[pair r] dy[token
    of r]`` and the weights' gradient ``<dy[token of r], rows[r]>``, which
    goes back to its pair as a gather of scalars."""
    return _from_slots(rows, pos, held, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, order, pos, held):
    return (_combine(rows, weights, order, pos, held),
            (rows, weights, order, pos, held))


def _combine_bwd(res, dy):
    rows, weights, order, pos, held = res
    dy_rows = dy[order // held.shape[1]].astype(jnp.float32)
    # weights are zero where a pair is not held, so the tail's rows get zero;
    # the tail of ``rows`` is whatever no group wrote, and is masked
    d_rows = (dy_rows * weights.reshape(-1)[order][:, None]).astype(rows.dtype)
    dots = (dy_rows * rows.astype(jnp.float32)).sum(-1)
    return d_rows, jnp.where(held, dots[pos], 0), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_layer(x, w_gate, bias, w1, w3, w2, *, top_k: int,
              expert_offset: int = 0, norm_topk_prob: bool = True,
              routed_scaling_factor: float = 1.0):
    """The held experts' part of a routed-expert SwiGLU layer.

    ``x``: ``[..., D]`` in the compute dtype; ``w_gate``: ``[D, E]`` and
    ``bias``: ``[E]``, float32; ``w1``, ``w3``: ``[G, D, F]`` and ``w2``:
    ``[G, F, D]``, the experts ``expert_offset .. expert_offset + G`` of the
    ``E`` the router knows.  Returns ``(y, counts, sel)``: ``y`` of ``x``'s
    shape and dtype, ``counts``: ``[E]`` int32 rows sent to each of the ``E``
    experts by these tokens, ``sel``: ``[N, top_k]`` the selection."""
    lead, d = x.shape[:-1], x.shape[-1]
    e, g = w_gate.shape[1], w1.shape[0]
    if not 0 <= expert_offset <= e - g:
        raise ValueError(f"moe_layer: experts {expert_offset} .. "
                         f"{expert_offset + g} are not among the router's {e}")
    x = x.reshape(-1, d)
    n = x.shape[0]
    with jax.named_scope(_ROUTE):
        sel, weights, counts = route(x, w_gate, bias, top_k=top_k,
                                     norm_topk_prob=norm_topk_prob,
                                     scaling=routed_scaling_factor)
        local = sel - expert_offset
        held = (local >= 0) & (local < g)
        weights = jnp.where(held, weights, 0)
        # pairs by expert held, in token order within an expert; the pairs
        # whose expert is elsewhere last
        order = jnp.argsort(jnp.where(held, local, g).reshape(-1), stable=True
                            ).astype(jnp.int32)
        pos = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * top_k, dtype=jnp.int32), unique_indices=True
        ).reshape(n, top_k)
        group_sizes = jax.lax.dynamic_slice_in_dim(counts, expert_offset, g)
    with jax.named_scope(_EXPERTS):
        rows = _sorted_rows(x, order, pos, held)
        gate = _grouped_matmul(rows, w1, group_sizes)
        up = _grouped_matmul(rows, w3, group_sizes)
        act = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
               ).astype(x.dtype)
        out = _grouped_matmul(act, w2, group_sizes)
    with jax.named_scope(_COMBINE):
        y = _combine(out, weights, order, pos, held)
    return y.reshape(lead + (d,)), counts, sel
