"""Mamba-2's selective state-space scan in its chunked (SSD) form, and the
causal depthwise conv that feeds it.

Per head, with a state ``S`` in ``R^{P x N}``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + D x_t

The sequence is cut into chunks of ``chunk`` tokens (Dao & Gu 2024,
"Transformers are SSMs", section 6).  Inside a chunk the recurrence is the
masked matrix product ``y_q = sum_{s<=q} (C_q . B_s) exp(cum_q - cum_s)
dt_s x_s`` with ``cum`` the running sum of ``dt A``; every chunk leaves a
state behind, the states are carried from chunk to chunk by the same
recurrence one level up (``nc`` steps, written as one small triangular
product), and each token reads the state its chunk started from.  ``dt``,
``A``, the running sums and every decay are float32 whatever the compute
dtype, like softmax statistics: a running sum reaches hundreds and the
decays are differences of it.  The matrix products take the compute dtype
in and accumulate in float32.

Plain ``jax.numpy``, no kernel; the scan's backward pass is autodiff's, of
these products.  The arrays are kept the way the products read and write
them: chunks lead, then heads (or channels), and a chunk's tokens are the
last axis (:func:`ssd_chunked`, :func:`causal_conv_silu` on four axes), so
nothing between a mixer's two projections is re-tiled.  What crosses HBM is
in the compute dtype, float32 living between a load and a store, with two
exceptions, each written once a pass: the entering-state term (two products
cannot share a fusion) and, in the conv's backward, the gradient of the
pre-activation (``dx`` is made of its shifted reads).  On the v5e XLA makes
the in-chunk weights inside the product's fusion in the forward pass and
writes their gradient out in the backward; the ``optimization_barrier``s pin
the dtype of what is written where XLA would up-cast early (``PERF.md``, PR
28).  :func:`ssd_scan` is the same scan for tokens-major callers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["causal_conv_silu", "ssd_chunked", "ssd_scan"]


def _shift(x, by):
    """``x[b, c, e, q]`` read ``by`` tokens ahead (behind, if negative) along
    the tokens ``(c, q)``, zeros outside.  A chunk's edge reads its
    neighbour's; both reads are a pad and then a slice, which XLA fuses into
    the consumer (a slice that feeds a pad it writes out)."""
    c, q = x.shape[1], x.shape[3]
    if by == 0:
        return x
    if abs(by) > q and c > 1:
        raise ValueError(f"a shift of {by} tokens spans more than a chunk of {q}")
    j = min(abs(by), q)
    if by < 0:
        out = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (j, 0)))[..., :q]
        if c > 1:   # the chunk before, its last j tokens in front
            out = out + jnp.pad(x, ((0, 0), (1, 0), (0, 0), (0, q)))[
                :, :c, :, q - j:2 * q - j]
        return out
    out = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, j)))[..., j:]
    if c > 1:
        out = out + jnp.pad(x, ((0, 0), (0, 1), (0, 0), (q, 0)))[
            :, 1:, :, j:j + q]
    return out


def _taps_views(x, w):
    """What tap ``k`` multiplies, in float32: ``x`` read ``W - 1 - k`` tokens
    behind."""
    return [_shift(x, k - (w - 1)).astype(jnp.float32) for k in range(w)]


def _conv_pre(views, taps, bias):
    """The float32 pre-activation ``bias + sum_k taps[k] x[. - (W-1) + k]``."""
    return sum(v * tap[:, None] for v, tap in zip(views, taps)) + bias[:, None]


@jax.custom_vjp
def _conv_silu(x, taps, bias):
    pre = _conv_pre(_taps_views(x, taps.shape[0]), taps, bias)
    return jax.nn.silu(pre).astype(x.dtype)


def _conv_fwd(x, taps, bias):
    return _conv_silu(x, taps, bias), (x, taps, bias)


def _conv_bwd(res, dy):
    # its own copies of the operands: without them XLA merges this
    # recomputation with the forward's (rematerialised beside it) and keeps
    # the float32 pre-activation and shifted copies of x between the two
    x, taps, bias = jax.lax.optimization_barrier(res)
    w = taps.shape[0]
    views = _taps_views(x, w)
    pre = _conv_pre(views, taps, bias)
    sig = jax.nn.sigmoid(pre)
    dpre = dy.astype(jnp.float32) * (sig * (1 + pre * (1 - sig)))
    dtaps = jnp.stack([jnp.sum(dpre * v, axis=(0, 1, 3)) for v in views])
    # token t feeds the outputs t .. t + W - 1 through the taps W-1 .. 0
    dx = sum(_shift(dpre, j) * taps[w - 1 - j][:, None] for j in range(w))
    return dx.astype(x.dtype), dtaps, jnp.sum(dpre, axis=(0, 1, 3))


_conv_silu.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(x, taps, bias):
    """``silu(causal depthwise conv(x) + bias)`` along the tokens.

    ``x``: ``[batch, T, channels]``, or ``[batch, chunks, channels, Q]`` with
    the tokens running along ``(chunks, Q)``, in the compute dtype; ``taps``:
    ``[W, channels]`` and ``bias``: ``[channels]``, float32.  Token ``t``
    reads tokens ``t - W + 1 .. t`` (zeros before the first).  Products, sums
    and the SiLU are float32 between the load of ``x`` and the store of the
    result, which has ``x``'s shape and dtype.  The backward pass keeps ``x``,
    the taps and the bias and nothing of ``x``'s size in float32: it
    recomputes the pre-activation, ``W`` multiply-adds an element, in one
    pass with the sums for the taps and the bias, and makes ``dx`` in a
    second from the shifted reads of that pass's float32 gradient.
    """
    if x.ndim == 3:
        y = _conv_silu(x.transpose(0, 2, 1)[:, None], taps, bias)
        return y[:, 0].transpose(0, 2, 1)
    return _conv_silu(x, taps, bias)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _product(spec, a, b):
    """``einsum(spec, a, b)`` accumulated in float32.  Its backward pass is
    the two transposed products, which take the cotangent in the operands'
    dtype as well: at the default precision the MXU rounds a float32 operand
    to that anyway, and this way it is the rounded array that crosses HBM."""
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _product_fwd(spec, a, b):
    return _product(spec, a, b), (a, b)


def _product_bwd(spec, res, g):
    a, b = res
    operands, out = spec.split("->")
    sa, sb = operands.split(",")
    g = g.astype(a.dtype)
    return (_product(f"{out},{sb}->{sa}", g, b).astype(a.dtype),
            _product(f"{sa},{out}->{sb}", a, g).astype(b.dtype))


_product.defvjp(_product_fwd, _product_bwd)


def ssd_scan(x, dt, A, B, C, D=None, *, chunk: int = 256):
    """``y`` of the recurrence above, zero initial state.

    ``x``: ``[batch, T, H, P]``; ``dt``: ``[batch, T, H]``, positive (after
    the softplus); ``A``: ``[H]``, negative; ``B``, ``C``: ``[batch, T, G,
    N]`` with ``G`` dividing ``H`` (every ``H / G`` heads share a group);
    ``D``: ``[H]`` or ``None``.  Returns ``x``'s shape and dtype.  ``T``
    need not be a multiple of ``chunk``: the tail is padded with ``dt = 0``
    tokens, which neither decay nor feed the state.
    """
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    if h % g or C.shape != B.shape or dt.shape != (b, t, h):
        raise ValueError(f"ssd_scan: x {x.shape}, dt {dt.shape}, B {B.shape}, "
                         f"C {C.shape} do not fit together")
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (t + pad) // q
    # [b, T, ...] -> [b, nc, ..., q]
    cut = lambda a: jnp.moveaxis(a.reshape((b, nc, q) + a.shape[2:]), 2, -1)
    y = ssd_chunked(cut(x), cut(dt), A, cut(B), cut(C), D)
    return jnp.moveaxis(y, -1, 2).reshape(b, t + pad, h, p)[:, :t]


def ssd_chunked(x, dt, A, B, C, D=None):
    """:func:`ssd_scan` on a sequence already cut into chunks, heads leading
    and a chunk's tokens last: ``x``: ``[batch, chunks, H, P, Q]``, ``dt``:
    ``[batch, chunks, H, Q]``, ``B``, ``C``: ``[batch, chunks, G, N, Q]``.
    Returns ``x``'s shape and dtype: the in-chunk term, the entering-state
    term and ``D x`` summed in float32 and rounded once."""
    b, nc, h, p, q = x.shape
    g, n = B.shape[2:4]
    f32, cdt = jnp.float32, x.dtype
    r = h // g                                          # heads per group

    # as it arrives, in its own dtype: XLA otherwise hoists the up-cast for
    # D x over the reshape and writes a float32 copy of x
    xc = jax.lax.optimization_barrier(x.reshape(b, nc, g, r, p, q))
    dtc = dt.astype(f32).reshape(b, nc, g, r, q)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(g, r, 1), axis=-1)

    # inside a chunk: weights[q, s] = (C_q . B_s) exp(cum_q - cum_s) dt_s, s <= q
    scores = _product("bcgnq,bcgns->bcgqs", C, B)
    causal = jnp.tril(jnp.ones((q, q), bool))
    # masked before the exp: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    weights = (scores[:, :, :, None] * decay * dtc[..., None, :]).astype(cdt)
    y = _product("bcgrps,bcgrqs->bcgrpq", xc, weights)

    # the state a chunk leaves behind: sum_s exp(cum_end - cum_s) dt_s x_s (x) B_s
    to_end = jnp.exp(cum[..., -1:] - cum) * dtc         # [b, nc, g, r, s]
    xw = (xc * to_end[..., None, :]).astype(cdt)
    states = _product("bcgrps,bcgns->bcgrpn", xw, B)

    # between chunks: the state chunk c starts from is
    # sum_{z<c} exp(total_{z+1} + .. + total_{c-1}) states_z
    total = jnp.cumsum(cum[..., -1], axis=1)            # [b, nc, g, r]
    start = jnp.pad(total, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[:, :, None, None]
    carry = jnp.exp(jnp.where(earlier, start[:, :, None] - total[:, None],
                              -jnp.inf))                # [b, c, z, g, r]
    entering = jnp.einsum("bczgr,bzgrpn->bcgrpn", carry, states,
                          precision=jax.lax.Precision.HIGHEST)
    rest = (_product("bcgrpn,bcgnq->bcgrpq", entering.astype(cdt), C)
            * jnp.exp(cum)[..., None, :])
    if D is not None:
        rest = rest + D.astype(f32).reshape(g, r, 1, 1) * xc.astype(f32)
    # Two products cannot share a fusion, so one term crosses HBM in float32:
    # this one, with D x added where it is made (left to itself XLA adds D x
    # in the other product's fusion and writes a float32 copy of x for it).
    # The sum is rounded here, once; without a barrier XLA undoes the
    # rounding for a consumer that computes in float32.
    rest = jax.lax.optimization_barrier(rest)
    y = jax.lax.optimization_barrier((y + rest).astype(cdt))
    return y.reshape(b, nc, h, p, q)
