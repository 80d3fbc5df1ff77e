"""A routed-expert (mixture of experts) layer that is told which experts it
holds: a sigmoid or a softmax router, top-k, no capacity, no drops.

The layer routes over **all** ``E`` experts and computes the part of the
result that the ``G`` experts it holds give (experts ``offset .. offset + G``
of ``E``).  With the sigmoid router (the default)::

    s   = sigmoid(W_g r)                        # float32, [E]
    sel = top_k(s + b)                          # b: a selection bias, not trained
    w   = s[sel] / (sum s[sel] + 1e-6) * scale  # from the unbiased scores
    out = sum_{e in sel, e held here} w_e W2_e (act(W1_e u) * W3_e u)

and with the softmax router (``score="softmax"``, no bias)::

    l   = W_g r                                 # float32 logits, [E]
    sel = top_k(l);  w = softmax(l[sel]) * scale

which is the softmax over all ``E`` renormalised over the ``k`` selected,
computed from the ``k`` logits alone.  ``act`` is SiLU (SwiGLU, the default)
or ReLU (ReGLU).  ``r`` is what the router reads, ``u`` what the experts
read: the same array unless the caller gives the router its own
(``router_in``).

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
result goes back as a weighted sum over each token's slots.  Both directions
of both row movements are gathers (by the sort's permutation one way, by its
inverse the other): no scatter-add on the path.

**The row bound and what is walked.**  The arrays in the sorted order have
the static worst case of rows, every pair held here (``N k``); a step holds
``group_sizes.sum()`` of them, a quarter where the layer holds a quarter of
the experts.  The kernels skip the tail by themselves.  Every other pass
over such an array (``_walk_rows``) runs over blocks of ``_ROW_BLOCK`` rows
under a loop whose trip count is the rows held this call, rounded up to a
block, and leaves the blocks past them as they are (not zeros: every caller
masks the tail, as it masks the kernels' tail): the gather of the sorted
rows, ``act(gate) * up`` and its backward, the backward of the weighted sum
(the gather of ``dy`` by the sort, its product with the weights, the row
dots) and the sum of the rows' two gradients through ``w1`` and ``w3``.
Each is a custom VJP, since such a loop has no reverse-mode rule; the
backward passes write in place of an operand they have read.  The kernels
are called once, outside every loop.  Where the bound is within one block,
or no whole number of blocks, the same functions run on the whole arrays.
**Not walked:** the passes indexed by token (one gather of ``[N, D]`` a slot
on the way back and in the gradient to ``u``, the scalars gathered by
``pos``) and the router.  ``moe_layer`` returns the rows it walked.

Gradients flow through the gathers, the grouped products (input and weight
gradients by group, the kernels' own backward rules) and the weights ``w``
into the router; none through the selection, and none to the bias, which is
the caller's state.  With ``router_in`` given the router's gradient goes to
it and the rows' to ``x``.

**Routing once a step.**  A caller that wraps the layer in a checkpoint saves
its input only, and its backward then routes a second time: scores, top-k and
sort, none of which carries a gradient.  Everything the backward reads of the
route carries the name ``ROUTED`` (``jax.ad_checkpoint.checkpoint_name``), so
a checkpoint whose policy is ``save_only_these_names(ROUTED)`` keeps it and
recomputes the layer from after the route: the selection and the selected
scores (logits, under the softmax), which is all ``route``'s own backward
rule reads of the ``[N, E]`` score matrix (autodiff would want all of it),
the weights with the pairs held elsewhere zeroed, which pairs are held, the
sort's permutation (and its inverse in ``moe_layer``) and the rows of each
expert held.  That is ``N k`` values six or seven times over and ``G``
counts: 6.1 MB a layer at 16,384 tokens and 22 a token, 1.4 MB at 4 a token,
against the 32 MB of one ``[16384, 512]`` score matrix.  Under any other
checkpoint, or none, the names do nothing.

Named scopes (metadata, like ``training.PHASE_SCOPES``): ``apex.moe.route``
(scores, top-k, weights, sort, counts), ``apex.moe.experts`` (gather, grouped
products, activation), ``apex.moe.combine``; the caller puts ``apex.moe``
around the call.

**The latent layer** (:func:`latent_moe_layer`) is the same layer for experts
that live in a latent space: the router reads one array (the hidden state)
and the experts another (its projection), and an expert is two products
around ``relu(.) ** 2`` with no gate.  It shares the router and the sort, and
**its row arrays follow the load**: with 22 experts a token and 8 of 512 held
the static worst case is 64 times the rows a step holds, so the sorted rows
are run in waves of ``_WAVE_ROWS`` rows under a loop whose trip count is the
rows held, rounded up to a wave.  A wave gathers its rows, runs the two
grouped products over its part of every group, and adds its weighted rows to
their tokens; no array has more rows than a wave, and still no pair is
dropped at any load (every pair held here is ``N k / _WAVE_ROWS`` waves).
The loop has no reverse-mode rule: the chain is a custom VJP that keeps its
inputs and runs the waves again backward, each recomputing its own forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..normalization.fused_layer_norm import _use_pallas

__all__ = ["MOE_SCOPES", "LATENT_SCOPES", "ROUTED", "SCORES", "ACTIVATIONS",
           "route", "moe_layer", "latent_moe_layer"]

#: rows a tile of the grouped-matmul kernel: a group's edge inside a tile
#: costs the tile twice, so smaller tiles lose less to uneven groups
_ROW_TILE = 256

#: rows a block of the walked passes (``_walk_rows``), a multiple of
#: ``_ROW_TILE`` so that a tile the kernels touch lies in walked rows
_ROW_BLOCK = 8 * _ROW_TILE

#: the scopes of the expert layer, outermost first
MOE_SCOPES = ("apex.moe", "apex.moe.route", "apex.moe.experts",
              "apex.moe.combine")
_ROUTE, _EXPERTS, _COMBINE = MOE_SCOPES[1:]

#: what a latent layer's caller adds inside ``apex.moe``: the projections
#: into and out of the latent space, and the shared expert
LATENT_SCOPES = ("apex.moe.latent", "apex.moe.shared")

#: the name (``jax.ad_checkpoint.checkpoint_name``) of what a layer's backward
#: reads of the route: a checkpoint around the layer whose policy saves it
#: (``save_only_these_names(ROUTED)``) recomputes no score, top-k or sort
ROUTED = "apex.moe.routed"
_kept = functools.partial(checkpoint_name, name=ROUTED)

#: rows a wave of the latent layer's expert chain, a multiple of ``_ROW_TILE``:
#: two to three times the rows a layer holds where it holds 8 of 512 experts
#: for 16,384 tokens at 22 a token, so that an uneven router stays one wave
_WAVE_ROWS = 64 * _ROW_TILE


def _logits(x, w_gate):
    return jnp.dot(x.astype(jnp.float32), w_gate,
                   precision=jax.lax.Precision.HIGHEST)


def _normalised(picked, norm_topk_prob, scaling):
    if norm_topk_prob:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    return picked * scaling


def _chosen(sel, experts):
    """``sel == expert`` as ``[N, k, E]``."""
    return sel[..., None] == jnp.arange(experts, dtype=sel.dtype)


#: the routers ``route`` has (its ``score``)
SCORES = ("sigmoid", "softmax")


def _top(values, ranked, top_k):
    """``(sel, values[sel], mask)``: the top ``top_k`` of ``ranked`` a
    token and the selected ``values``, both kept under ``ROUTED``, and
    ``sel == expert`` as ``[N, k, E]``.  The
    values are read through a mask: the same numbers (the other terms of a
    sum are zeros) as one pass of compares, and its transpose another, where
    a gather's is a scatter-add of N k scalars into [N, E] (on the v5e 4 ns
    an element and twice that: 1.55 and 3.1 ms at 16,384 tokens, 22 of 512;
    ``PERF.md``, PR 33)."""
    _, sel = jax.lax.top_k(ranked, top_k)
    mask = _chosen(sel, values.shape[1])
    picked = jnp.where(mask, values[:, None, :], 0).sum(-1)
    # what the backward reads of the scores: k a token, not E
    return _kept(sel), _kept(picked), mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _route(x, w_gate, bias, top_k, norm_topk_prob, scaling):
    return _route_fwd(x, w_gate, bias, top_k, norm_topk_prob, scaling)[0]


def _route_fwd(x, w_gate, bias, top_k, norm_topk_prob, scaling):
    scores = jax.nn.sigmoid(_logits(x, w_gate))
    sel, picked, mask = _top(scores, scores + bias, top_k)
    return ((sel, _normalised(picked, norm_topk_prob, scaling),
             mask.sum((0, 1), dtype=jnp.int32)), (x, w_gate, sel, picked))


def _route_bwd(top_k, norm_topk_prob, scaling, res, cotangents):
    """Autodiff's gradient to the bit, from the selected scores alone: a
    token's ``k`` experts are distinct, so every sum the mask makes, there
    and here, has one term that is not zero."""
    x, w_gate, sel, picked = res
    d_picked, = jax.vjp(lambda p: _normalised(p, norm_topk_prob, scaling),
                        picked)[1](cotangents[1])
    d_picked = d_picked * (picked * (1 - picked))       # the sigmoid's
    return _to_router(x, w_gate, sel, d_picked) + (None,)


def _to_router(x, w_gate, sel, d_picked):
    """The gradients of the router's input and weight from those of the
    selected logits: spread to ``[N, E]`` through the mask, then the two
    transposes of the product."""
    d_logits = jnp.where(_chosen(sel, w_gate.shape[1]), d_picked[..., None],
                         0).sum(1)
    d_x, = jax.linear_transpose(lambda a: _logits(a, w_gate), x)(d_logits)
    d_w, = jax.linear_transpose(lambda w: _logits(x, w), w_gate)(d_logits)
    return d_x, d_w


_route.defvjp(_route_fwd, _route_bwd)


def _softmax_weights(picked, scaling):
    return jax.nn.softmax(picked, axis=-1) * scaling


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _route_softmax(x, w_gate, top_k, scaling):
    return _route_softmax_fwd(x, w_gate, top_k, scaling)[0]


def _route_softmax_fwd(x, w_gate, top_k, scaling):
    logits = _logits(x, w_gate)
    sel, picked, mask = _top(logits, logits, top_k)
    return ((sel, _softmax_weights(picked, scaling),
             mask.sum((0, 1), dtype=jnp.int32)), (x, w_gate, sel, picked))


def _route_softmax_bwd(top_k, scaling, res, cotangents):
    """From the ``k`` selected logits a token: ``dl[sel] = w (dw - sum w
    dw)`` (the softmax's own rule, by ``jax.vjp``), zero elsewhere."""
    x, w_gate, sel, picked = res
    d_picked, = jax.vjp(lambda p: _softmax_weights(p, scaling),
                        picked)[1](cotangents[1])
    return _to_router(x, w_gate, sel, d_picked)


_route_softmax.defvjp(_route_softmax_fwd, _route_softmax_bwd)


def route(x, w_gate, bias, *, top_k: int, norm_topk_prob: bool = True,
          scaling: float = 1.0, score: str = "sigmoid"):
    """``(sel, weights, counts)`` for tokens ``x``: ``[N, D]``.

    ``w_gate``: ``[D, E]`` and ``bias``: ``[E]``, float32.  The scores are
    float32 at full matmul precision: the fourth and fifth of 64 sigmoid
    scores differ by a few thousandths for many tokens, so a rounded score is
    another selection.  ``sel``: ``[N, top_k]`` int32, by ``scores + bias``;
    ``weights``: ``[N, top_k]`` float32, the unbiased scores of the selected
    experts, normalised over the ``top_k`` when ``norm_topk_prob``, times
    ``scaling``; ``counts``: ``[E]`` int32, the rows each expert was sent.
    The selection carries no gradient, and the weights' gradient is a rule
    of its own that keeps ``sel`` and the ``top_k`` selected scores a token
    (under ``ROUTED``) where autodiff would keep all ``E``.

    ``score="softmax"``: ``sel`` is the top ``top_k`` of the float32 logits
    (the top of their softmax) and ``weights`` the softmax of the selected
    logits times ``scaling`` (the softmax over all ``E`` renormalised over
    the ``top_k``), its rule keeping the ``top_k`` logits a token.  No
    selection bias (``bias`` is None) and the weights always normalised."""
    if score not in SCORES:
        raise ValueError(f"moe.route: score {score!r} is none of {SCORES}")
    if score == "softmax" and (bias is not None or not norm_topk_prob):
        raise ValueError(
            "moe.route: the softmax router takes no selection bias and "
            "normalises its weights over the top_k (norm_topk_prob)")
    bias_dtype = None if bias is None else bias.dtype
    if w_gate.dtype != jnp.float32 or bias_dtype not in (None, jnp.float32):
        raise TypeError(
            f"moe.route: the router's weight arrived as {w_gate.dtype} and "
            f"the selection bias as {bias_dtype}; the scores are float32 "
            f"whatever the compute dtype (keep the router out of the amp "
            f"cast: models.lfm2_moe.keep_fp32)")
    if score == "softmax":
        return _route_softmax(x, w_gate, top_k, scaling)
    return _route(x, w_gate, bias, top_k, norm_topk_prob, scaling)


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


def _unwritten(shape, dtype, after):
    """What a walk's result holds before its blocks are written, and after
    them in the blocks the walk never reaches: an allocation, no fill
    (``AllocateBuffer`` in the TPU's compiled module), made once ``after``, a
    traced scalar that is never negative, is known.  The branch is what holds
    it there: an allocation with no operand is moved to the head of the
    compiled step, where the sixteen of a step were live at once (3.5 GiB; a
    rehearsal compile of the LFM2 cell's step, ``PERF.md``, PR 32)."""
    return jax.lax.cond(after >= 0, lambda: jax.lax.empty(shape, dtype),
                        lambda: jnp.zeros(shape, dtype))


def _whole(bound):
    """Whether arrays of ``bound`` rows are passed over whole: within one
    block, or no whole number of blocks (a decision by shape)."""
    return bound <= _ROW_BLOCK or bound % _ROW_BLOCK != 0


def _rows_walked(n_rows, bound):
    """The rows of ``bound`` that ``_walk_rows`` goes over for ``n_rows``
    held (a traced count): whole blocks, or all of them."""
    if _whole(bound):
        return jnp.int32(bound)
    return (n_rows + _ROW_BLOCK - 1) // _ROW_BLOCK * _ROW_BLOCK


def _walk_rows(fn, n_rows, *row_arrays, in_place=0):
    """``fn(*row_arrays)``, a tuple of arrays whose row ``r`` depends on row
    ``r`` of each argument alone, computed a block of ``_ROW_BLOCK`` rows at a
    time over the blocks that hold the first ``n_rows`` rows (a traced
    count).  The first ``in_place`` results take the place of the first
    ``in_place`` arguments, of their shape and dtype, block by block; the
    others are written into ``_unwritten`` arrays.  The blocks past
    ``n_rows`` are left as they were: the tail's contract of
    ``_grouped_matmul``.  A loop with a traced trip count has no reverse-mode
    rule, so every caller is a custom VJP.  Where the arrays are no longer
    than one block, or not whole blocks, ``fn`` runs on them whole."""
    bound = row_arrays[0].shape[0]
    if _whole(bound):
        return fn(*row_arrays)
    shapes = jax.eval_shape(fn, *(
        jax.ShapeDtypeStruct((_ROW_BLOCK,) + a.shape[1:], a.dtype)
        for a in row_arrays))

    def body(i, results):
        start = i * _ROW_BLOCK
        blocks = fn(*(jax.lax.dynamic_slice_in_dim(a, start, _ROW_BLOCK)
                      for a in results[:in_place] + row_arrays[in_place:]))
        return tuple(jax.lax.dynamic_update_slice_in_dim(r, b, start, 0)
                     for r, b in zip(results, blocks))
    return jax.lax.fori_loop(
        0, _rows_walked(n_rows, bound) // _ROW_BLOCK, body,
        row_arrays[:in_place] + tuple(
            _unwritten((bound,) + s.shape[1:], s.dtype, n_rows)
            for s in shapes[in_place:]))


@jax.custom_vjp
def _sorted_rows(x, order, pos, held, n_rows):
    """Row ``r`` of the result is the token of pair ``order[r]``: ``x[order //
    k]`` for ``x``: ``[N, D]`` and ``N k`` pairs, over the first ``n_rows``
    rows.  Backward: the inverse permutation, as gathers, summed over a
    token's slots."""
    k = held.shape[1]
    return _walk_rows(lambda o: (x[o // k],), n_rows, order)[0]


def _sorted_rows_fwd(x, order, pos, held, n_rows):
    return _sorted_rows(x, order, pos, held, n_rows), (pos, held)


def _sorted_rows_bwd(res, g):
    pos, held = res
    return _from_slots(g, pos, held).astype(g.dtype), None, None, None, None


_sorted_rows.defvjp(_sorted_rows_fwd, _sorted_rows_bwd)


@jax.custom_vjp
def _gate_up(rows, w1, w3, group_sizes, n_rows):
    """``(rows @ w1, rows @ w3)`` by group.  Backward: the kernels' own
    rules; the two gradients of ``rows`` are added over the first ``n_rows``
    rows, where autodiff would add the whole bound."""
    return (_grouped_matmul(rows, w1, group_sizes),
            _grouped_matmul(rows, w3, group_sizes))


def _gate_up_fwd(rows, w1, w3, group_sizes, n_rows):
    product = lambda r, w: _grouped_matmul(r, w, group_sizes)
    gate, gate_vjp = jax.vjp(product, rows, w1)
    up, up_vjp = jax.vjp(product, rows, w3)
    return (gate, up), (gate_vjp, up_vjp, n_rows)


def _gate_up_bwd(res, g):
    gate_vjp, up_vjp, n_rows = res
    (by_gate, d_w1), (by_up, d_w3) = gate_vjp(g[0]), up_vjp(g[1])
    d_rows, = _walk_rows(lambda a, b: (a + b,), n_rows, by_gate, by_up,
                         in_place=1)
    return d_rows, d_w1, d_w3, None, None


_gate_up.defvjp(_gate_up_fwd, _gate_up_bwd)


#: the expert's gate: SwiGLU's SiLU or ReGLU's ReLU
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gated(gate, up, kind):
    """``(act(gate) * up,)`` in float32, rounded once: a tuple, as
    ``_walk_rows`` takes its functions."""
    act = ACTIVATIONS[kind](gate.astype(jnp.float32))
    return (act * up.astype(jnp.float32)).astype(gate.dtype),


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _activation(gate, up, n_rows, kind):
    """``act(gate) * up`` in float32, rounded once, over the first ``n_rows``
    rows; backward over the same rows, from ``gate`` and ``up``."""
    return _walk_rows(functools.partial(_gated, kind=kind), n_rows, gate,
                      up)[0]


def _activation_fwd(gate, up, n_rows, kind):
    return _activation(gate, up, n_rows, kind), (gate, up, n_rows)


def _activation_bwd(kind, res, d_act):
    gate, up, n_rows = res
    block = lambda g, u, d: jax.vjp(functools.partial(_gated, kind=kind),
                                    g, u)[1]((d,))
    return _walk_rows(block, n_rows, gate, up, d_act, in_place=2) + (None,)


_activation.defvjp(_activation_fwd, _activation_bwd)


@jax.custom_vjp
def _combine(rows, weights, order, pos, held, n_rows):
    """``y[n] = sum_s weights[n, s] rows[pos[n, s]]`` over the slots that are
    held, float32 sums rounded once.  Backward, in the sorted order over the
    first ``n_rows`` rows: one gather of ``dy`` by ``order`` serves ``d
    rows[r] = weights[pair r] dy[token of r]`` and the weights' gradient
    ``<dy[token of r], rows[r]>``, which goes back to its pair as a gather of
    scalars."""
    return _from_slots(rows, pos, held, weights).astype(rows.dtype)


def _combine_fwd(rows, weights, order, pos, held, n_rows):
    return (_combine(rows, weights, order, pos, held, n_rows),
            (rows, weights, order, pos, held, n_rows))


def _combine_bwd(res, dy):
    rows, weights, order, pos, held, n_rows = res
    k, by_pair = held.shape[1], weights.reshape(-1)

    def block(rows, order):
        dy_rows = dy[order // k].astype(jnp.float32)
        # weights are zero where a pair is not held, so the rows between the
        # last one held and the block's end get zero
        return ((dy_rows * by_pair[order][:, None]).astype(rows.dtype),
                (dy_rows * rows.astype(jnp.float32)).sum(-1))
    # the tail of ``rows`` is whatever no group wrote, the tail of ``dots``
    # what no block wrote: masked
    d_rows, dots = _walk_rows(block, n_rows, rows, order, in_place=1)
    return (d_rows, jnp.where(held, dots[pos], 0), None, None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _route_and_sort(x, w_gate, bias, g, *, top_k, expert_offset,
                    norm_topk_prob, scaling, whole=1, score="sigmoid"):
    """:func:`route`, then the (token, slot) pairs by expert held: ``(sel,
    weights, counts, held, order, group_sizes)`` with ``weights`` zero where
    a pair's expert is elsewhere, ``order`` the pairs by expert held, in
    token order within an expert, the pairs whose expert is elsewhere last,
    then pairs that are no token's up to a multiple of ``whole``, and
    ``group_sizes``: ``[g]`` the rows of each expert held.  The last four
    are what a layer's backward reads of all this, and carry ``ROUTED``."""
    e = w_gate.shape[1]
    if not 0 <= expert_offset <= e - g:
        raise ValueError(f"moe_layer: experts {expert_offset} .. "
                         f"{expert_offset + g} are not among the router's {e}")
    # the default passes no ``score``: the controls under ``tests/benchmark``
    # put routers of their own in ``route``'s place, by name
    extra = {} if score == "sigmoid" else {"score": score}
    sel, weights, counts = route(x, w_gate, bias, top_k=top_k,
                                 norm_topk_prob=norm_topk_prob,
                                 scaling=scaling, **extra)
    local = sel - expert_offset
    held = _kept((local >= 0) & (local < g))
    order = jnp.argsort(jnp.where(held, local, g).reshape(-1), stable=True
                        ).astype(jnp.int32)
    order = jnp.concatenate([order, order.shape[0] + jnp.arange(
        -order.shape[0] % whole, dtype=order.dtype)])
    weights = jax.lax.select(held, weights, jnp.zeros_like(weights))
    return (sel, _kept(weights), counts, held, _kept(order),
            _kept(jax.lax.dynamic_slice_in_dim(counts, expert_offset, g)))


def moe_layer(x, w_gate, bias, w1, w3, w2, *, top_k: int,
              expert_offset: int = 0, norm_topk_prob: bool = True,
              routed_scaling_factor: float = 1.0, score: str = "sigmoid",
              activation: str = "silu", router_in=None):
    """The held experts' part of a routed-expert gated layer (SwiGLU, or
    ReGLU with ``activation="relu"``).

    ``x``: ``[..., D]`` in the compute dtype; ``w_gate``: ``[D, E]`` and
    ``bias``: ``[E]``, float32 (None under ``score="softmax"``: see
    :func:`route`); ``w1``, ``w3``: ``[G, D, F]`` and ``w2``: ``[G, F, D]``,
    the experts ``expert_offset .. expert_offset + G`` of the ``E`` the
    router knows.  ``router_in``: ``[..., D]``, what the router reads where
    it is not ``x`` (the same tokens; gradients flow to both).  Returns
    ``(y, counts, sel, rows_walked)``: ``y`` of ``x``'s shape and dtype,
    ``counts``: ``[E]`` int32 rows sent to each of the ``E`` experts by
    these tokens, ``sel``: ``[N, top_k]`` the selection, ``rows_walked``:
    int32, the rows of the ``N top_k`` the row passes went over (the rows
    held, rounded up to a block; all of them where they are within one
    block or not whole blocks)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"moe_layer: activation {activation!r} is none of "
                         f"{tuple(ACTIVATIONS)}")
    lead, d = x.shape[:-1], x.shape[-1]
    g = w1.shape[0]
    x = x.reshape(-1, d)
    n = x.shape[0]
    if router_in is None:
        reads = x
    elif router_in.shape[:-1] != lead:
        raise ValueError(f"moe_layer: the router reads {router_in.shape} and "
                         f"the experts {lead + (d,)}: not the same tokens")
    else:
        reads = router_in.reshape(n, router_in.shape[-1])
    with jax.named_scope(_ROUTE):
        sel, weights, counts, held, order, group_sizes = _route_and_sort(
            reads, w_gate, bias, g, top_k=top_k, expert_offset=expert_offset,
            norm_topk_prob=norm_topk_prob, scaling=routed_scaling_factor,
            score=score)
        pos = _kept(jnp.zeros_like(order).at[order].set(
            jnp.arange(n * top_k, dtype=jnp.int32), unique_indices=True
        ).reshape(n, top_k))
        n_rows = group_sizes.sum()
    with jax.named_scope(_EXPERTS):
        rows = _sorted_rows(x, order, pos, held, n_rows)
        gate, up = _gate_up(rows, w1, w3, group_sizes, n_rows)
        out = _grouped_matmul(_activation(gate, up, n_rows, activation), w2,
                              group_sizes)
    with jax.named_scope(_COMBINE):
        y = _combine(out, weights, order, pos, held, n_rows)
    return (y.reshape(lead + (d,)), counts, sel,
            _rows_walked(n_rows, n * top_k))


def _relu2_chain(rows, w1, w2, group_sizes):
    """``relu(rows @ w1) ** 2 @ w2`` by group, the square in float32, rounded
    once.  The rows past the last group are whatever no group wrote."""
    hidden = _grouped_matmul(rows, w1, group_sizes)
    act = jnp.square(jax.nn.relu(hidden.astype(jnp.float32)))
    return _grouped_matmul(act.astype(rows.dtype), w2, group_sizes)


def _wave(i, wave, order, group_sizes, k):
    """What wave ``i`` of ``wave`` sorted rows holds: the pairs of its rows
    (``order`` has whole waves), which of them are rows held (the others are
    the tail's), their tokens, and its part of every group."""
    start = i * wave
    pairs = jax.lax.dynamic_slice_in_dim(order, start, wave)
    ends = jnp.cumsum(group_sizes)
    live = start + jnp.arange(wave, dtype=jnp.int32) < ends[-1]
    inside = lambda edge: jnp.clip(edge - start, 0, wave)
    return pairs, live, pairs // k, inside(ends) - inside(ends - group_sizes)


def _n_waves(group_sizes, wave):
    return (group_sizes.sum() + wave - 1) // wave


@jax.custom_vjp
def _latent_chain(latent, weights, w1, w2, order, group_sizes):
    """``y[n] = sum_s weights[n, s] relu(latent[n] @ w1[e]) ** 2 @ w2[e]``
    over the slots ``s`` of token ``n`` whose expert ``e`` is held (the
    others have weight zero), float32 sums rounded once, computed a wave of
    ``order``'s rows at a time over the waves that hold rows.  ``order``
    comes as whole waves."""
    k = weights.shape[1]
    wave = min(_WAVE_ROWS, order.shape[0])
    by_pair = weights.reshape(-1)

    def body(i, total):
        pairs, live, tokens, sizes = _wave(i, wave, order, group_sizes, k)
        with jax.named_scope(_EXPERTS):
            out = _relu2_chain(latent[tokens], w1, w2, sizes)
        with jax.named_scope(_COMBINE):
            # the tail holds what no group wrote: masked, not multiplied
            out = jnp.where(live[:, None], out.astype(jnp.float32)
                            * by_pair[pairs][:, None], 0)
            return total.at[tokens].add(out)
    total = jax.lax.fori_loop(0, _n_waves(group_sizes, wave), body,
                              jnp.zeros(latent.shape, jnp.float32))
    return total.astype(latent.dtype)


def _latent_chain_fwd(latent, weights, w1, w2, order, group_sizes):
    return (_latent_chain(latent, weights, w1, w2, order, group_sizes),
            (latent, weights, w1, w2, order, group_sizes))


def _latent_chain_bwd(res, dy):
    """The waves again: each recomputes its rows' forward, takes ``dy`` of
    its rows' tokens, and adds to the gradients of ``latent`` (by token), of
    the two weights (by group) and of the pairs' weights."""
    latent, weights, w1, w2, order, group_sizes = res
    k = weights.shape[1]
    wave = min(_WAVE_ROWS, order.shape[0])
    by_pair = weights.reshape(-1)

    def body(i, sums):
        d_latent, d_sorted, d_w1, d_w2 = sums
        pairs, live, tokens, sizes = _wave(i, wave, order, group_sizes, k)
        with jax.named_scope(_EXPERTS):
            out, chain_vjp = jax.vjp(
                lambda r, a, b: _relu2_chain(r, a, b, sizes),
                latent[tokens], w1, w2)
        with jax.named_scope(_COMBINE):
            dy_rows = dy[tokens].astype(jnp.float32)
            dots = jnp.where(live, (dy_rows * out.astype(jnp.float32)).sum(-1),
                             0)
            d_out = jnp.where(live[:, None], dy_rows * by_pair[pairs][:, None],
                              0).astype(out.dtype)
        with jax.named_scope(_EXPERTS):
            d_rows, by_w1, by_w2 = chain_vjp(d_out)
            d_rows = jnp.where(live[:, None], d_rows.astype(jnp.float32), 0)
        return (d_latent.at[tokens].add(d_rows),
                jax.lax.dynamic_update_slice_in_dim(d_sorted, dots, i * wave,
                                                    0),
                d_w1 + by_w1.astype(jnp.float32),
                d_w2 + by_w2.astype(jnp.float32))
    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    d_latent, d_sorted, d_w1, d_w2 = jax.lax.fori_loop(
        0, _n_waves(group_sizes, wave), body,
        (zeros(latent), zeros(order), zeros(w1), zeros(w2)))
    # back to the pairs' own order, once: a pair appears once in ``order``,
    # and the rows no wave reached hold their zeros
    d_pairs = zeros(order).at[order].set(d_sorted, unique_indices=True)
    return (d_latent.astype(latent.dtype),
            d_pairs[:weights.size].reshape(weights.shape).astype(weights.dtype),
            d_w1.astype(w1.dtype), d_w2.astype(w2.dtype), None, None)


_latent_chain.defvjp(_latent_chain_fwd, _latent_chain_bwd)


def latent_moe_layer(x, latent, w_gate, bias, w1, w2, *, top_k: int,
                     expert_offset: int = 0, norm_topk_prob: bool = True,
                     routed_scaling_factor: float = 1.0):
    """The held experts' part of a routed layer of non-gated ``relu ** 2``
    experts that live in a latent space, between its two latent projections
    (the caller's).

    ``x``: ``[..., D]``, what the router reads; ``latent``: ``[..., L]``, what
    the experts read, both in the compute dtype; ``w_gate``: ``[D, E]`` and
    ``bias``: ``[E]``, float32; ``w1``: ``[G, L, F]`` and ``w2``: ``[G, F,
    L]``, the experts ``expert_offset .. expert_offset + G`` of the ``E`` the
    router knows.  Returns ``(y, counts, sel, (rows_held, rows_computed))``:
    ``y`` of ``latent``'s shape and dtype, ``counts``: ``[E]`` int32 rows sent
    to each of the ``E`` experts by these tokens, ``sel``: ``[N, top_k]`` the
    selection, and the rows of the ``N top_k`` that are held here and that
    the waves went over (the rows held, rounded up to a wave), both int32."""
    lead, n_lat = latent.shape[:-1], latent.shape[-1]
    if x.shape[:-1] != lead:
        raise ValueError(f"latent_moe_layer: the router reads {x.shape} and "
                         f"the experts {latent.shape}: not the same tokens")
    x, latent = x.reshape(-1, x.shape[-1]), latent.reshape(-1, n_lat)
    wave = min(_WAVE_ROWS, x.shape[0] * top_k)
    with jax.named_scope(_ROUTE):
        # whole waves: the pairs past the last are no token's (a gather
        # clips them, a scatter-add adds their zeros) and never live
        sel, weights, counts, _, order, group_sizes = _route_and_sort(
            x, w_gate, bias, w1.shape[0], top_k=top_k,
            expert_offset=expert_offset, norm_topk_prob=norm_topk_prob,
            scaling=routed_scaling_factor, whole=wave)
    y = _latent_chain(latent, weights, w1, w2, order, group_sizes)
    n_rows = group_sizes.sum()
    return (y.reshape(lead + (n_lat,)), counts, sel,
            (n_rows, _n_waves(group_sizes, wave) * wave))
