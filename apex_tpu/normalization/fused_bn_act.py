"""BatchNorm epilogue: normalize + affine + residual-add + ReLU.

    ``y = relu((x - mean) * invstd * scale + bias [+ z])``

The conv-path analog of :mod:`.fused_layer_norm`, and the apex contrib
``groupbn`` contract (``bn_relu`` / ``bn_add_relu``, ``csrc/groupbn/*``).
Statistics (batch mean/var, the cross-replica psum, running-stat updates)
are computed by the caller; this module owns the elementwise tail and its
backward.

Two implementations of the same mathematics:

* **XLA** (``_fwd_ref``/``_bwd_ref``, the automatic choice): plain jnp on
  the activation in the shape it arrives in, the per-channel vectors
  broadcast over the last axis, so the compiler fuses the tail into its
  neighbours and no layout change stands between a convolution and its
  BatchNorm.  Measured on the v5e inside the ResNet-50 amp-O2 step
  (``PERF.md`` section 6, PR 26): the Mosaic kernel below ran at a fifth
  of its HBM roofline and, as a custom call, cost a physical
  ``[N,H,W,C] -> [rows,C]`` copy per site and fenced the fusions around
  it; at no ResNet-50 shape did it win.
* **Mosaic** (``impl="pallas"``, or ``interpret=True`` on the CPU): one
  Pallas pass over ``[rows, C]``.  Kept for the tests, the tuner and
  ``chip_smoke.py``'s kernel sweep; nothing selects it automatically.

Both sit under one ``custom_vjp`` that saves ``x`` and ``z`` and
recomputes the ReLU mask, and ``_fwd_ref``/``_bwd_ref`` double as the
test oracle.

The backward treats ``mean``/``invstd`` as independent differentiable
inputs: their cotangents flow back into the caller's statistics, so
autodiff of the *whole* BN (stats + epilogue) remains exact.  On the
Mosaic side the kernel computes the two activation-sized outputs
(dx, dz) and the per-channel reductions stay jnp.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_compat import align_vma as _align_vma
from ..pallas_compat import match_vma as _match_vma
from ..pallas_compat import sds_with_vma as _sds
from ..tune import space as _space
from ..tune.dispatch import kernel_config as _tuned_config
from .fused_layer_norm import _use_pallas

__all__ = ["bn_relu_residual", "bn_act_epilogue_ref"]

#: config-cache version of this kernel's blocking scheme (ISSUE 14).
TUNE_VERSION = 1


# -- the XLA implementation (also the oracle) ---------------------------------
#
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


# -- pallas kernels -----------------------------------------------------------
#
# NHWC input reshaped to [rows = N*H*W, C]; per-channel vectors ride as
# [C] blocks replicated across grid steps (the fused_layer_norm w/b
# pattern, transposed: here the broadcast is per COLUMN).

_ROW_BLOCK = 256


def _pick_rows(n_rows: int, c: int, bytes_per_elem: int,
               row_block: Optional[int] = None) -> int:
    # shared VMEM/row-block math (ISSUE 14 satellite): one home in
    # apex_tpu.tune.space for this kernel, fused_layer_norm, and the
    # autotuner's constraint checker; row_block is the tuned cap.
    return _space.pick_rows(n_rows, c, bytes_per_elem,
                            row_block=row_block or _ROW_BLOCK)


def _kernel_fits(c: int, itemsize: int) -> bool:
    """Even the 8-row floor block must fit the scoped-VMEM budget (the
    fused_layer_norm width gate, per-channel edition)."""
    # fwd worst case: x, z, out at itemsize + ~2 fp32 temporaries
    return _space.floor_block_fits(c, 3 * itemsize + 8)


def tune_bucket(n_rows: int, c: int, itemsize: int, has_z: bool) -> str:
    """Config-cache shape bucket: rows round to a power of two; channel
    width, itemsize, and the residual flag (an extra activation-sized
    operand per block) are exact."""
    return f"r{_space.pow2_bucket(n_rows)}_c{c}_i{itemsize}_z{int(has_z)}"


def _fwd_kernel(x_ref, mean_ref, invstd_ref, w_ref, b_ref, z_ref, out_ref,
                *, affine, has_z, relu):
    xf = x_ref[:].astype(jnp.float32)                    # [R, C]
    out = (xf - mean_ref[:]) * invstd_ref[:]             # [C] broadcasts
    if affine:
        out = out * w_ref[:] + b_ref[:]
    if has_z:
        out = out + z_ref[:].astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    out_ref[:] = out.astype(out_ref.dtype)


def _bwd_kernel(g_ref, x_ref, mean_ref, invstd_ref, w_ref, b_ref, z_ref,
                dx_ref, dz_ref, *, affine, has_z, relu):
    xf = x_ref[:].astype(jnp.float32)
    gf = g_ref[:].astype(jnp.float32)
    if relu:
        pre = (xf - mean_ref[:]) * invstd_ref[:]
        if affine:
            pre = pre * w_ref[:] + b_ref[:]
        if has_z:
            pre = pre + z_ref[:].astype(jnp.float32)
        gf = jnp.where(pre > 0, gf, 0.0)
    s = w_ref[:] if affine else 1.0
    dx_ref[:] = (gf * s * invstd_ref[:]).astype(dx_ref.dtype)
    if has_z:
        dz_ref[:] = gf.astype(dz_ref.dtype)
    else:
        dz_ref[:] = jnp.zeros_like(dz_ref)


def _as_2d(v, c):
    """Per-channel vector as a [1, C] fp32 block (Mosaic wants lane-tiled
    >= 2-D operands, like the xentropy kernel's [R, 1] columns)."""
    return jnp.reshape(jnp.asarray(v, jnp.float32), (1, c))


def _pallas_fwd(x2d, mean, invstd, scale, bias, z2d, relu, interpret,
                row_block=None):
    n, c = x2d.shape
    isz = jnp.dtype(x2d.dtype).itemsize
    rows = _pick_rows(n, c, 3 * isz + 8, row_block)
    grid = (pl.cdiv(n, rows),)
    affine = scale is not None
    has_z = z2d is not None
    w = _as_2d(scale if affine else jnp.zeros((c,)), c)
    b = _as_2d(bias if affine else jnp.zeros((c,)), c)
    zz = z2d if has_z else jnp.zeros((1, c), x2d.dtype)
    vec = pl.BlockSpec((1, c), lambda i: (0, 0))
    mat = pl.BlockSpec((rows, c), lambda i: (i, 0))
    kernel = functools.partial(_fwd_kernel, affine=affine, has_z=has_z,
                               relu=relu)
    # Mosaic under shard_map(check_vma=True) needs operands agreeing on
    # how they vary — replicated per-channel vectors next to sharded
    # activations are the textbook mix (see pallas_compat.align_vma).
    operands = _align_vma(x2d, _as_2d(mean, c), _as_2d(invstd, c), w, b,
                          zz)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat, vec, vec, vec, vec,
                  mat if has_z else vec],
        out_specs=mat,
        out_shape=_sds((n, c), x2d.dtype, *operands),
        interpret=interpret,
    )(*operands)


def _pallas_bwd(g2d, x2d, mean, invstd, scale, bias, z2d, relu, interpret,
                row_block=None):
    n, c = x2d.shape
    isz = jnp.dtype(x2d.dtype).itemsize
    rows = _pick_rows(n, c, 4 * isz + 12,      # g, x, dx, dz + temporaries
                      row_block)
    grid = (pl.cdiv(n, rows),)
    affine = scale is not None
    has_z = z2d is not None
    w = _as_2d(scale if affine else jnp.zeros((c,)), c)
    b = _as_2d(bias if affine else jnp.zeros((c,)), c)
    zz = z2d if has_z else jnp.zeros((1, c), x2d.dtype)
    vec = pl.BlockSpec((1, c), lambda i: (0, 0))
    mat = pl.BlockSpec((rows, c), lambda i: (i, 0))
    kernel = functools.partial(_bwd_kernel, affine=affine, has_z=has_z,
                               relu=relu)
    dz_dtype = z2d.dtype if has_z else x2d.dtype
    operands = _align_vma(g2d, x2d, _as_2d(mean, c), _as_2d(invstd, c),
                          w, b, zz)
    dx, dz = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mat, mat, vec, vec, vec, vec,
                  mat if has_z else vec],
        out_specs=[mat, mat],
        out_shape=[_sds((n, c), x2d.dtype, *operands),
                   _sds((n, c), dz_dtype, *operands)],
        interpret=interpret,
    )(*operands)
    return dx, (dz if has_z else None)


# -- dispatch -----------------------------------------------------------------

def _dispatch_pallas(c: int, impl: Optional[str], itemsize: int) -> bool:
    """True when the Mosaic kernel is what runs.

    The automatic choice (``impl=None``) is XLA at every shape: inside the
    ResNet-50 amp-O2 step on the v5e the kernel never won (``PERF.md``
    section 6, PR 26).  A class of shapes a later measurement earns goes
    here, as a predicate on what this function is given."""
    if impl not in (None, "pallas", "jnp"):
        raise ValueError(
            f"impl must be None, 'pallas', or 'jnp'; got {impl!r}")
    return impl == "pallas" and _use_pallas() and _kernel_fits(c, itemsize)


# -- public op with custom VJP ------------------------------------------------
#
# ``x``/``z`` are ``[rows, C]`` on the Mosaic side and in the caller's own
# shape on the XLA side; the per-channel operands are ``[C]`` on both.

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _epilogue(x, mean, invstd, scale, bias, z, relu, use_pallas,
              interpret, row_block):
    if use_pallas:
        return _pallas_fwd(x, mean, invstd, scale, bias, z, relu,
                           interpret, row_block)
    return _fwd_ref(x, mean, invstd, scale, bias, z, relu)


def _epilogue_fwd(x, mean, invstd, scale, bias, z, relu, use_pallas,
                  interpret, row_block):
    out = _epilogue(x, mean, invstd, scale, bias, z, relu, use_pallas,
                    interpret, row_block)
    return out, (x, mean, invstd, scale, bias, z)


def _epilogue_bwd(relu, use_pallas, interpret, row_block, res, g):
    x, mean, invstd, scale, bias, z = res
    dx, d_mean, d_invstd, d_scale, d_bias, dz = _bwd_ref(
        g, x, mean, invstd, scale, bias, z, relu)
    if use_pallas:
        # the activation-sized pair comes from the kernel; the
        # per-channel column sums above stay jnp
        dx, dz = _pallas_bwd(g, x, mean, invstd, scale, bias, z, relu,
                             interpret, row_block)
    # Per-channel operands are usually replicated over a data axis the
    # activations are sharded on: their column sums are per-shard here
    # and must arrive summed (see pallas_compat.match_vma).
    return tuple(_match_vma(ct, p) for ct, p in zip(
        (dx, d_mean, d_invstd, d_scale, d_bias, dz), res))


_epilogue.defvjp(_epilogue_fwd, _epilogue_bwd)


def bn_relu_residual(x, mean, invstd, scale=None, bias=None, z=None,
                     relu=True, impl: Optional[str] = None,
                     interpret: bool = False,
                     row_block: Optional[int] = None):
    """BN epilogue: ``relu((x - mean) * invstd * scale + bias + z)``.

    ``x`` is channels-last (``[..., C]``); ``mean``/``invstd`` and the
    optional affine ``scale``/``bias`` are per-channel ``[C]`` (or any
    shape broadcastable to it — stat-shaped ``[1, 1, 1, C]`` inputs are
    flattened); ``z`` is an optional residual with ``x``'s shape, added
    BEFORE the ReLU (the apex ``bn_add_relu`` contract).  Returns
    ``x.dtype``; all arithmetic accumulates in fp32.

    ``impl``: ``None`` and ``"jnp"`` run the XLA implementation on ``x``
    as it is (no reshape: the compiler fuses it with its neighbours);
    ``"pallas"`` forces the Mosaic kernel on the TPU, over ``x``
    reshaped to ``[rows, C]``.  ``interpret=True`` runs that kernel in
    interpreter mode (CPU tier-parity tests).

    Differentiable in ``x``, ``mean``, ``invstd``, ``scale``, ``bias``
    and ``z`` — statistics computed outside (XLA reductions, psums for
    SyncBatchNorm) receive exact cotangents, so wrapping only the
    epilogue keeps full-BN autodiff correct.

    ``row_block``: explicit kernel row-block cap; left ``None`` the
    per-device config cache (:mod:`apex_tpu.tune`) is consulted with
    the hard-coded 256-row default as the fallback.
    """
    c = x.shape[-1]
    mean = jnp.ravel(jnp.asarray(mean, jnp.float32))
    invstd = jnp.ravel(jnp.asarray(invstd, jnp.float32))
    if scale is not None:
        scale = jnp.ravel(jnp.asarray(scale, jnp.float32))
        bias = jnp.ravel(jnp.asarray(bias, jnp.float32))
    isz = jnp.dtype(x.dtype).itemsize
    use_pallas = _dispatch_pallas(c, impl, isz)
    if interpret and impl != "jnp":
        use_pallas = True
    if not use_pallas:
        return _epilogue(x, mean, invstd, scale, bias, z, bool(relu),
                         False, False, None)
    n_rows = x.size // c
    if row_block is None:
        cfg = _tuned_config("bn_relu_residual", TUNE_VERSION,
                            tune_bucket(n_rows, c, isz, z is not None),
                            params=("row_block",))
        if cfg:
            row_block = cfg["row_block"]
    out = _epilogue(x.reshape(n_rows, c), mean, invstd, scale, bias,
                    z.reshape(n_rows, c) if z is not None else None,
                    bool(relu), True, bool(interpret), row_block)
    return out.reshape(x.shape)
