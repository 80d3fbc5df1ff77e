"""Rotary positions (RoPE) on queries and keys, and the per-head RMSNorm that
some models put before them.

Half-split convention (GPT-NeoX, and every ``rotate_half`` model since): a
head of size ``d`` is two halves ``[x1 | x2]``, pair ``i`` is ``(x1_i, x2_i)``
and turns by ``pos * theta ** (-2 i / d)``::

    rope(x) = [x1 cos - x2 sin | x2 cos + x1 sin]

The angles, their sines and cosines and the rotation itself are float32
whatever the compute dtype (a bf16 angle at position 4,095 is off by up to
eight positions); the result is rounded once, to ``x``'s dtype.  Plain
``jax.numpy``: an elementwise pass between a projection and the attention
kernel, which XLA fuses with the norm in front of it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..normalization.rms_norm import rms_norm

__all__ = ["rope_angles", "apply_rope", "qk_norm_rope"]


def rope_angles(positions, head_dim: int, theta: float = 10000.0):
    """``positions[..., None] * theta ** (-2 i / head_dim)`` for the
    ``head_dim / 2`` pairs of a head, float32: ``[..., head_dim // 2]``."""
    if head_dim % 2:
        raise ValueError(f"rope pairs the halves of a head: head_dim "
                         f"{head_dim} is odd")
    pair = jnp.arange(head_dim // 2, dtype=jnp.float32)
    inv_freq = jnp.exp(pair * (-2.0 / head_dim) * jnp.log(jnp.float32(theta)))
    return jnp.asarray(positions, jnp.float32)[..., None] * inv_freq


def _cos_sin(x, positions, theta):
    """Cosines and sines for ``x``: ``[batch, T, heads, head_dim]``, shaped to
    broadcast over the heads: ``[.., T, 1, head_dim // 2]``."""
    if positions is None:
        positions = jnp.arange(x.shape[1])
    angles = rope_angles(positions, x.shape[-1], theta)[..., None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rotate(x32, cos, sin):
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, positions=None, *, theta: float = 10000.0):
    """Rotate ``x``: ``[batch, T, heads, head_dim]`` by its positions.

    ``positions``: ``[T]`` or ``[batch, T]`` (default ``0 .. T-1``).  Float32
    between the load and the store; returns ``x``'s shape and dtype.  The
    rotation is orthogonal, so its backward pass is the rotation by the
    negative angles, which autodiff derives from these lines."""
    return _rotate(x.astype(jnp.float32),
                   *_cos_sin(x, positions, theta)).astype(x.dtype)


def qk_norm_rope(q, k, q_weight, k_weight, positions=None, *,
                 theta: float = 10000.0, eps: float = 1e-5):
    """``rope(RMSNorm_head(q))``, ``rope(RMSNorm_head(k))``: each head is
    normalised over its own ``head_dim`` with one weight of ``[head_dim]`` for
    all query heads and one for all key heads, then rotated.  ``q``:
    ``[batch, T, heads, head_dim]``, ``k``: ``[batch, T, kv_heads,
    head_dim]``.  One float32 pass each: the norm's result is not rounded
    before the rotation."""
    cos, sin = _cos_sin(q, positions, theta)
    turn = lambda x, w: _rotate(
        rms_norm(x.astype(jnp.float32), w, eps), cos, sin).astype(x.dtype)
    return turn(q, q_weight), turn(k, k_weight)
