"""The gated short convolution of the LFM2 family: a causal depthwise conv of
a few taps between two gates.

With ``B``, ``C`` and ``x`` three equal slices of one projection::

    y_t = C_t * sum_k taps[k] * (B * x)_{t - (W-1) + k}

(zeros before the first token; no bias, no activation).  It reads its
neighbours the way :func:`apex_tpu.ops.causal_conv_silu` does and shares that
function's shifted views (``ops.ssd._shift``): tokens are the last axis of
what it works on, a chunk's edge reads the chunk before.

Float32 between the loads and the store.  The backward pass has a rule of
its own because of what a shifted view costs: in an XLA fusion every
pad-then-slice view of an operand is a read of that operand (``PERF.md``, PR
28).  Autodiff of the lines above would read ``B`` and ``x`` once per tap
three times over (for ``dC``, for the taps' gradient, and for the forward it
recomputes).  The rule keeps the conv's result (in the compute dtype, written
beside ``y`` by the same pass) and shifts only the incoming gradient::

    g      = dy * C                               # float32, never written
    dC     = dy * conv                            # conv as the forward kept it
    du_t   = sum_k taps[k] g_{t + (W-1) - k}
    dB, dx = du * x, du * B
    dtaps[k] = sum_t g_{t + (W-1) - k} (B x)_t    # B x unshifted, read once

so ``B`` and ``x`` are read once, unshifted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ssd import _shift, _taps_views

__all__ = ["gated_short_conv"]


def _conv(b, x, taps):
    """Float32 ``sum_k taps[k] (b x)[. - (W-1) + k]`` on ``[b, c, e, q]``."""
    bx = b.astype(jnp.float32) * x.astype(jnp.float32)
    return sum(v * tap[:, None]
               for v, tap in zip(_taps_views(bx, taps.shape[0]), taps))


@jax.custom_vjp
def _gated(b, c, x, taps):
    return _gated_fwd(b, c, x, taps)[0]


def _gated_fwd(b, c, x, taps):
    conv = _conv(b, x, taps)
    y = (c.astype(jnp.float32) * conv).astype(x.dtype)
    return y, (b, c, x, taps, conv.astype(x.dtype))


def _gated_bwd(res, dy):
    b, c, x, taps, conv = res
    w, f32 = taps.shape[0], jnp.float32
    dy = dy.astype(f32)
    g = dy * c.astype(f32)
    # token t feeds the outputs t .. t + W - 1 through the taps W-1 .. 0
    ahead = [_shift(g, w - 1 - k) for k in range(w)]
    du = sum(v * tap[:, None] for v, tap in zip(ahead, taps))
    b32, x32 = b.astype(f32), x.astype(f32)
    bx = b32 * x32
    dtaps = jnp.stack([jnp.sum(v * bx, axis=(0, 1, 3)) for v in ahead])
    return ((du * x32).astype(b.dtype), (dy * conv.astype(f32)).astype(c.dtype),
            (du * b32).astype(x.dtype), dtaps.astype(taps.dtype))


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_short_conv(b, c, x, taps):
    """``c * causal depthwise conv(b * x)`` along the tokens.

    ``b``, ``c``, ``x``: ``[batch, T, channels]``, or ``[batch, chunks,
    channels, Q]`` with the tokens running along ``(chunks, Q)``, all three
    of one shape and dtype; ``taps``: ``[W, channels]``, float32.  Token
    ``t`` reads tokens ``t - W + 1 .. t`` (zeros before the first).  Returns
    ``x``'s shape and dtype.  See the module docstring for what the backward
    pass keeps and reads."""
    if not (b.shape == c.shape == x.shape and b.dtype == c.dtype == x.dtype):
        raise ValueError(f"gated_short_conv: b {b.shape} {b.dtype}, c "
                         f"{c.shape} {c.dtype} and x {x.shape} {x.dtype} "
                         f"must agree")
    if x.ndim == 3:
        chunked = lambda a: a.transpose(0, 2, 1)[:, None]
        y = _gated(chunked(b), chunked(c), chunked(x), taps)
        return y[:, 0].transpose(0, 2, 1)
    return _gated(b, c, x, taps)
