"""Fused conv-side BN epilogue: normalize + affine + residual-add + ReLU.

The conv-path analog of :mod:`.fused_layer_norm` (ISSUE 7).  The r05
roofline ledger puts ResNet-50 amp O2 at ~93% of HBM peak with the MXU
only ~25% busy: the step is *memory*-bound, and a large share of the
traffic is the elementwise ``bn -> relu -> (+residual)`` chains between
convolutions — each a separate read-modify-write sweep over the
activation tensor when left to generic fusion.  The reference attacks
exactly this with the apex contrib ``groupbn`` persistent NHWC kernels
(``bn_relu`` / ``bn_add_relu`` epilogues, ``csrc/groupbn/*``); the
TPU-native equivalent is ONE Pallas pass:

    ``y = relu((x - mean) * invstd * scale + bias [+ z])``

Statistics (batch mean/var, the cross-replica psum, running-stat
updates) stay in XLA — they are channel reductions XLA schedules well
and they carry the SyncBatchNorm collective contract; the kernel owns
only the elementwise epilogue, where the bytes are.

Structure mirrors ``fused_layer_norm.py``/``contrib/xentropy``: a jnp
reference (``_fwd_ref``/``_bwd_ref``) that doubles as the CPU fallback
and the test oracle, Pallas forward/backward kernels with a
``custom_vjp`` around them, and interpreter mode (``interpret=True``)
so CPU tests exercise the REAL kernel against the reference
(tier-parity, ISSUE 7 satellite).

The backward treats ``mean``/``invstd`` as independent differentiable
inputs: their cotangents flow back into the XLA-side statistics
computation, so autodiff of the *whole* BN (stats + epilogue) remains
exact — the kernel never needs the Welford transpose.  Per-channel
reductions (d_scale, d_bias, d_mean, d_invstd) are column sums XLA
already does optimally and stay as jnp ops fused into the same program;
the kernel computes the two activation-sized outputs (dx, dz) in one
pass.
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


# -- reference math (jnp fallback + oracle) -----------------------------------
#
# Kept op-for-op identical to the tail SyncBatchNorm historically inlined
# (normalize fp32, affine, + z, relu, cast back) so routing the module
# through this function is a bitwise no-op on the jnp path.

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
    """
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    if relu:
        pre = (xf - mean) * invstd
        if scale is not None:
            pre = pre * scale + bias
        if z is not None:
            pre = pre + z.astype(jnp.float32)
        gf = jnp.where(pre > 0, gf, 0.0)
    s = scale if scale is not None else jnp.float32(1.0)
    dx = (gf * s * invstd).astype(x.dtype)
    dz = gf.astype(z.dtype) if z is not None else None
    red = tuple(range(x.ndim - 1))          # all but the channel axis
    xmu = xf - mean
    d_scale = (jnp.sum(gf * xmu * invstd, axis=red)
               if scale is not None else None)
    d_bias = jnp.sum(gf, axis=red) if bias is not None else None
    d_mean = -jnp.sum(gf * s, axis=red) * jnp.ravel(invstd)
    d_invstd = jnp.sum(gf * s * xmu, axis=red)
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

# In-context crossover, same lesson as fused_layer_norm's: below a few
# million elements the custom call is a fusion barrier that costs more
# than it saves.  Conv-side activations at benchmark shapes (b128 x 56^2
# x 256 = ~100M elements) sit far above it.
_JNP_MAX_ELEMENTS = 2 * 1024 * 1024


def _dispatch_pallas(n_rows: int, c: int, impl: Optional[str],
                     itemsize: int) -> bool:
    if impl not in (None, "pallas", "jnp"):
        raise ValueError(
            f"impl must be None, 'pallas', or 'jnp'; got {impl!r}")
    if not _use_pallas() or not _kernel_fits(c, itemsize):
        return False
    if impl is not None:
        return impl == "pallas"
    return n_rows * c >= _JNP_MAX_ELEMENTS


# -- public op with custom VJP ------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _epilogue(x2d, mean, invstd, scale, bias, z2d, relu, use_pallas,
              interpret, row_block):
    if use_pallas:
        return _pallas_fwd(x2d, mean, invstd, scale, bias, z2d, relu,
                           interpret, row_block)
    return _fwd_ref(x2d, mean, invstd, scale, bias, z2d, relu)


def _epilogue_fwd(x2d, mean, invstd, scale, bias, z2d, relu, use_pallas,
                  interpret, row_block):
    out = _epilogue(x2d, mean, invstd, scale, bias, z2d, relu, use_pallas,
                    interpret, row_block)
    return out, (x2d, mean, invstd, scale, bias, z2d)


def _epilogue_bwd(relu, use_pallas, interpret, row_block, res, g):
    x2d, mean, invstd, scale, bias, z2d = res
    if use_pallas:
        dx, dz = _pallas_bwd(g, x2d, mean, invstd, scale, bias, z2d, relu,
                             interpret, row_block)
        # Per-channel reductions recompute the relu mask in jnp — column
        # sums XLA fuses with the kernel's outputs; the activation-sized
        # work stayed in the Pallas pass.
        _, d_mean, d_invstd, d_scale, d_bias, _ = _bwd_ref(
            g, x2d, mean, invstd, scale, bias, z2d, relu)
    else:
        dx, d_mean, d_invstd, d_scale, d_bias, dz = _bwd_ref(
            g, x2d, mean, invstd, scale, bias, z2d, relu)
    # mean/invstd cotangents keep their input shapes ([1, C] rows here).
    d_mean = jnp.reshape(d_mean, jnp.shape(mean)).astype(
        jnp.asarray(mean).dtype)
    d_invstd = jnp.reshape(d_invstd, jnp.shape(invstd)).astype(
        jnp.asarray(invstd).dtype)
    if scale is not None:
        d_scale = jnp.reshape(d_scale, jnp.shape(scale)).astype(
            jnp.asarray(scale).dtype)
        d_bias = jnp.reshape(d_bias, jnp.shape(bias)).astype(
            jnp.asarray(bias).dtype)
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
    """Fused BN epilogue: ``relu((x - mean) * invstd * scale + bias + z)``.

    ``x`` is channels-last (``[..., C]``); ``mean``/``invstd`` and the
    optional affine ``scale``/``bias`` are per-channel ``[C]`` (or any
    shape broadcastable to it — stat-shaped ``[1, 1, 1, C]`` inputs are
    flattened); ``z`` is an optional residual with ``x``'s shape, added
    BEFORE the ReLU (the apex ``bn_add_relu`` contract).  Returns
    ``x.dtype``; all arithmetic accumulates in fp32.

    ``impl``: ``None`` picks pallas-vs-jnp by size (pallas only on TPU);
    ``"pallas"``/``"jnp"`` force a path.  ``interpret=True`` runs the
    Pallas kernel in interpreter mode (CPU tier-parity tests).

    Differentiable in ``x``, ``mean``, ``invstd``, ``scale``, ``bias``
    and ``z`` — statistics computed outside (XLA reductions, psums for
    SyncBatchNorm) receive exact cotangents, so wrapping only the
    epilogue keeps full-BN autodiff correct.

    ``row_block``: explicit kernel row-block cap; left ``None`` the
    per-device config cache (:mod:`apex_tpu.tune`) is consulted with
    the hard-coded 256-row default as the fallback.
    """
    c = x.shape[-1]
    n_rows = 1
    for s in x.shape[:-1]:
        n_rows *= s
    x2d = x.reshape(n_rows, c)
    z2d = z.reshape(n_rows, c) if z is not None else None
    mean = jnp.ravel(jnp.asarray(mean, jnp.float32))
    invstd = jnp.ravel(jnp.asarray(invstd, jnp.float32))
    if scale is not None:
        scale = jnp.ravel(jnp.asarray(scale, jnp.float32))
        bias = jnp.ravel(jnp.asarray(bias, jnp.float32))
    isz = jnp.dtype(x2d.dtype).itemsize
    use_pallas = _dispatch_pallas(n_rows, c, impl, isz)
    if interpret and impl != "jnp":
        use_pallas = True
    if use_pallas and row_block is None:
        cfg = _tuned_config("bn_relu_residual", TUNE_VERSION,
                            tune_bucket(n_rows, c, isz, z is not None),
                            params=("row_block",))
        if cfg:
            row_block = cfg["row_block"]
    out = _epilogue(x2d, mean, invstd, scale, bias, z2d, bool(relu),
                    use_pallas, bool(interpret), row_block)
    return out.reshape(x.shape)
