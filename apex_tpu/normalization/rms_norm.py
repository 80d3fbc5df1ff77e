"""RMSNorm, and the gated RMSNorm of a Mamba-2 mixer.

``y = x * rsqrt(mean(x**2) + eps) * weight`` over the last axis, with the
statistics in float32 whatever the input's dtype (the reference library's
later ``FusedRMSNorm`` has the same contract).  The gated form multiplies
by ``silu(z)`` first and then normalises, over the whole axis (one group).

Plain ``jax.numpy``: a row-wise reduction between two matmuls is what XLA
fuses into their prologue and epilogue on the TPU (``PERF.md``, PR 24:
LayerNorm's backward rides in matmul fusions), so there is no kernel here.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


def rms_norm(x, weight=None, eps: float = 1e-5):
    """RMSNorm over the last axis; returns ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


def gated_rms_norm(x, z, weight=None, eps: float = 1e-5):
    """``rms_norm(x * silu(z))``: gate first, then one norm over the axis."""
    gated = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rms_norm(gated, weight, eps).astype(x.dtype)


def gated_rms_norm_factors(x, z, weight, eps: float = 1e-5, axis: int = -1):
    """:func:`gated_rms_norm` over ``axis`` as two factors, for a consumer
    that is linear along that axis: ``x * silu(z) * weight`` in ``x``'s dtype,
    and the float32 ``rsqrt(mean((x silu(z))**2) + eps)`` with ``axis`` kept.
    A token's factor commutes with a matrix product over ``axis``, so the
    caller scales the product's float32 sums by it: ``x`` and ``z`` are read
    once, and what is rounded to ``x``'s dtype is the same number up to that
    factor."""
    # x as it arrives: XLA otherwise hoists the up-cast over the reshapes
    # before it and writes a float32 copy of x for this fusion to read
    x = jax.lax.optimization_barrier(x)
    gated = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    inv_rms = jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=axis, keepdims=True) + eps)
    shape = [1] * x.ndim
    shape[axis] = x.shape[axis]
    out = (gated * weight.astype(jnp.float32).reshape(shape)).astype(x.dtype)
    # written once, beside the sum of squares: without the barrier XLA
    # recomputes the gate inside the consumer's product and, for the two
    # readers, writes a float32 copy of x
    return jax.lax.optimization_barrier(out), inv_rms


class RMSNorm(nn.Module):
    """``nn.RMSNorm``-semantics module; ``__call__(x, gate=None)`` applies
    :func:`gated_rms_norm` when a gate is given.  The weight is created
    float32 under the name ``scale`` (amp O2 keeps it float32)."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, gate=None):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        if gate is None:
            return rms_norm(x, scale, self.eps)
        return gated_rms_norm(x, gate, scale, self.eps)
