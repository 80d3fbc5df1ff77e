"""BatchNorm epilogue: normalize + affine + residual-add + ReLU.

    ``y = relu((x - mean) * invstd * scale + bias [+ z])``

The conv-path analog of :mod:`.fused_layer_norm`, and the apex contrib
``groupbn`` contract (``bn_relu`` / ``bn_add_relu``, ``csrc/groupbn/*``).
Statistics (batch mean/var, the cross-replica psum, running-stat updates)
are computed by the caller; this module owns the elementwise tail and its
backward.

There is no kernel here: a Mosaic pass over ``[rows, C]`` ran at a fifth
of its HBM roofline inside the ResNet-50 amp-O2 step on the v5e, cost a
copy per site and fenced XLA's fusions (``PERF.md`` section 6, PR 26).

Plain jnp on the activation in the shape it arrives in, the per-channel
vectors broadcast over the last axis, so the compiler fuses the tail
into its neighbours.  One ``custom_vjp`` saves ``x`` and ``z`` and
recomputes the ReLU mask in the backward.

The backward treats ``mean``/``invstd`` as independent differentiable
inputs: their cotangents flow back into the caller's statistics, so
autodiff of the *whole* BN (stats + epilogue) remains exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..pallas_compat import match_vma as _match_vma

__all__ = ["bn_relu_residual", "bn_act_epilogue_ref"]


# Rank-agnostic: ``x`` is ``[..., C]`` and the per-channel operands are
# ``[C]``, broadcast over the last axis.  Arithmetic in fp32, cast back.

def _fwd_ref(x, mean, invstd, scale, bias, z, relu):
    out = (x.astype(jnp.float32) - mean) * invstd
    if scale is not None:
        out = out * scale + bias
    if z is not None:
        out = out + z.astype(jnp.float32)
    if relu:
        out = jax.nn.relu(out)
    return out.astype(x.dtype)


def bn_act_epilogue_ref(x, mean, invstd, scale=None, bias=None, z=None,
                        relu=True):
    """Public alias of the jnp reference epilogue (the test oracle);
    same optional-affine signature as :func:`bn_relu_residual`."""
    return _fwd_ref(x, mean, invstd, scale, bias, z, relu)


def _bwd_ref(g, x, mean, invstd, scale, bias, z, relu):
    """Activation-sized grads (dx, dz) + per-channel reductions.

    With ``y = relu(xhat * scale + bias + z)`` and ``xhat = (x - mean) *
    invstd`` (mean/invstd independent inputs):

    * ``dx = g' * scale * invstd``          (``g' = g`` masked by y > 0)
    * ``dz = g'``
    * ``d_scale = sum(g' * xhat)``; ``d_bias = sum(g')``   (per channel)
    * ``d_mean = -sum(g' * scale) * invstd``
    * ``d_invstd = sum(g' * scale * (x - mean))``

    ``scale`` and ``invstd`` are constant along the reduced axes, so the
    four are two reductions, ``sum(g')`` and ``sum(g' * (x - mean))``,
    and per-channel products of them.
    """
    xmu = x.astype(jnp.float32) - mean
    if relu:
        pre = xmu * invstd
        if scale is not None:
            pre = pre * scale + bias
        if z is not None:
            pre = pre + z.astype(jnp.float32)
        # masked in g's own dtype: ONE activation-sized tensor is then
        # dz and what the sums and dx read, where a mask applied in
        # fp32 made XLA keep the mask as a further array beside g
        g = jnp.where(pre > 0, g, jnp.zeros_like(g))
    gf = g.astype(jnp.float32)
    s = scale if scale is not None else jnp.float32(1.0)
    dx = (gf * (s * invstd)).astype(x.dtype)
    dz = g.astype(z.dtype) if z is not None else None
    red = tuple(range(x.ndim - 1))          # all but the channel axis
    sum_g = jnp.sum(gf, axis=red)
    sum_gxmu = jnp.sum(gf * xmu, axis=red)
    invstd = jnp.ravel(invstd)
    d_scale = sum_gxmu * invstd if scale is not None else None
    d_bias = sum_g if bias is not None else None
    d_mean = -sum_g * s * invstd
    d_invstd = sum_gxmu * s
    return dx, d_mean, d_invstd, d_scale, d_bias, dz


# -- public op with custom VJP ------------------------------------------------

_epilogue = jax.custom_vjp(_fwd_ref, nondiff_argnums=(6,))


def _epilogue_fwd(x, mean, invstd, scale, bias, z, relu):
    out = _fwd_ref(x, mean, invstd, scale, bias, z, relu)
    return out, (x, mean, invstd, scale, bias, z)


def _epilogue_bwd(relu, res, g):
    x, mean, invstd, scale, bias, z = res
    cts = _bwd_ref(g, x, mean, invstd, scale, bias, z, relu)
    # Per-channel operands are usually replicated over a data axis the
    # activations are sharded on: their column sums are per-shard here
    # and must arrive summed (see pallas_compat.match_vma).
    return tuple(_match_vma(ct, p) for ct, p in zip(cts, res))


_epilogue.defvjp(_epilogue_fwd, _epilogue_bwd)


def bn_relu_residual(x, mean, invstd, scale=None, bias=None, z=None,
                     relu=True):
    """BN epilogue: ``relu((x - mean) * invstd * scale + bias + z)``.

    ``x`` is channels-last (``[..., C]``); ``mean``/``invstd`` and the
    optional affine ``scale``/``bias`` are per-channel ``[C]`` (or any
    shape broadcastable to it — stat-shaped ``[1, 1, 1, C]`` inputs are
    flattened); ``z`` is an optional residual with ``x``'s shape, added
    BEFORE the ReLU (the apex ``bn_add_relu`` contract).  Returns
    ``x.dtype``; all arithmetic accumulates in fp32.

    Differentiable in ``x``, ``mean``, ``invstd``, ``scale``, ``bias``
    and ``z`` — statistics computed outside (XLA reductions, psums for
    SyncBatchNorm) receive exact cotangents, so wrapping only the
    epilogue keeps full-BN autodiff correct.
    """
    mean = jnp.ravel(jnp.asarray(mean, jnp.float32))
    invstd = jnp.ravel(jnp.asarray(invstd, jnp.float32))
    if scale is not None:
        scale = jnp.ravel(jnp.asarray(scale, jnp.float32))
        bias = jnp.ravel(jnp.asarray(bias, jnp.float32))
    return _epilogue(x, mean, invstd, scale, bias, z, bool(relu))
