"""Mamba-2's selective state-space scan in its chunked (SSD) form.

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

Plain ``jax.numpy``; the backward pass is autodiff's, of these products.
On the v5e at the published widths (64 heads of 64, state 128, chunk 256)
XLA fuses the decay, mask and scaling into one pass that writes the
``[chunk, chunk]`` weights in the compute dtype (``PERF.md``, PR 27).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan"]


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
    f32, cdt = jnp.float32, x.dtype
    r = h // g                                          # heads per group

    xc = x.reshape(b, nc, q, g, r, p)
    Bc, Cc = B.reshape(b, nc, q, g, n), C.reshape(b, nc, q, g, n)
    # [b, nc, g, r, q]: heads lead, a chunk's tokens are the minor axis
    dtc = dt.astype(f32).reshape(b, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(g, r, 1), axis=-1)

    # inside a chunk: weights[q, s] = (C_q . B_s) exp(cum_q - cum_s) dt_s, s <= q
    scores = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc,
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((q, q), bool))
    # masked before the exp: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    weights = (scores[:, :, :, None] * decay * dtc[..., None, :]).astype(cdt)
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", weights, xc,
                   preferred_element_type=f32)

    # the state a chunk leaves behind: sum_s exp(cum_end - cum_s) dt_s x_s (x) B_s
    to_end = jnp.exp(cum[..., -1:] - cum) * dtc         # [b, nc, g, r, s]
    xw = (xc * to_end.transpose(0, 1, 4, 2, 3)[..., None]).astype(cdt)
    states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", Bc, xw,
                        preferred_element_type=f32)

    # between chunks: the state chunk c starts from is
    # sum_{z<c} exp(total_{z+1} + .. + total_{c-1}) states_z
    total = jnp.cumsum(cum[..., -1], axis=1)            # [b, nc, g, r]
    start = jnp.pad(total, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[:, :, None, None]
    carry = jnp.exp(jnp.where(earlier, start[:, :, None] - total[:, None],
                              -jnp.inf))                # [b, c, z, g, r]
    entering = jnp.einsum("bczgr,bzgrpn->bcgrpn", carry, states,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + (jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cc, entering.astype(cdt),
                        preferred_element_type=f32)
             * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None])

    y = y.reshape(b, t + pad, h, p)[:, :t]
    if D is not None:
        y = y + D.astype(f32)[:, None] * x[:, :t].astype(f32)
    return y.astype(cdt)
