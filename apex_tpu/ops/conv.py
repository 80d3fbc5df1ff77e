"""NHWC implicit-GEMM Pallas convolution with fused BN/ReLU/residual
epilogue (ISSUE 18).

**Nothing selects these kernels automatically.**  Measured on the v5e
inside the ResNet-50 amp-O2 step (``PERF.md`` section 6, PR 26), every
stride-1 site ran 18 to 23 times longer than the roofline of its shape,
and each custom call cost a ``jnp.pad``, a layout copy and the fusions
it fenced; XLA's own convolution is faster at every ResNet-50 shape.
``impl=None`` therefore runs ``lax.conv_general_dilated`` (with the
padding in its ``padding`` argument, never a ``jnp.pad``), and
:class:`PallasConv` runs at every site the XLA conv ``nn.Conv`` runs.
``impl="pallas"`` (on the TPU) and ``interpret=True`` (anywhere) still
run the kernels below: the tests, the tuner and ``chip_smoke.py``'s
kernel sweep do.

The kernels are the TPU analog of the implicit-GEMM formulation cuDNN
uses for the reference's NVIDIA convs: the im2col tile is materialized
**in VMEM only** — never in HBM — by a static shift-and-matmul tap loop,
with the :func:`apex_tpu.normalization.bn_relu_residual` epilogue in
the forward kernel's epilogue.

Kernel scheme (forward)
    grid ``(N, ceil(O/block_n), ceil(OH/boh))`` — the innermost axis
    streams output-row blocks, so the padded input image block
    ``[1, Hp, Wp, C]`` stays VMEM-resident for a whole ``(n, j)`` pass
    and the weight block ``[KH, KW, C, block_n]`` for a whole ``n``
    pass.  Each of the ``KH*KW`` taps is a strided slice of the resident
    image and one MXU matmul-accumulate into an fp32 ``[boh*OW,
    block_n]`` accumulator: exactly an im2col GEMM, with the im2col
    matrix never built.  ``boh = block_m // OW`` output rows per block
    (``block_m`` = the im2col row-tile, the tuned knob next to
    ``block_n``).

Backward (custom VJP)
    *dgrad* reuses the forward machinery on the stride-dilated cotangent
    with spatially rotated, in/out-transposed weights (a stride-1 conv);
    *wgrad* is a dedicated kernel on grid ``(ceil(O/block_n), N)`` whose
    ``[KH*KW, C, block_n]`` output block stays resident across the
    innermost batch axis and accumulates one tap-GEMM per (tap, image).
    Epilogue cotangents (d_mean/d_invstd/d_scale/d_bias/dz and the ReLU
    mask) reuse :func:`fused_bn_act._bwd_ref` on the saved
    pre-activation — per-channel column sums XLA fuses well — so the
    fused path is gradient-exact vs the explicit conv→bn_relu_residual
    chain.

Contract (the repo kernel contract, ISSUE 7/14):

* jnp reference :func:`conv2d_ref` (``lax.conv_general_dilated`` NHWC +
  the bn_act epilogue reference) is both the CPU fallback and the test
  oracle; ``interpret=True`` runs the REAL kernels in CPU tests.
* :data:`TUNE_VERSION` + a ``conv2d`` tune-registry spec
  (``block_m``/``block_n``, VMEM constraint via ``tune/space``,
  ledger-driven priority); the public function consults the per-device
  config cache at trace time when the caller left the blocks ``None``,
  with the hard-coded defaults as the zero-cost fallback.  Block
  partitioning never reorders a single output element's tap/K reduction,
  so tuned configs match the default BITWISE (``exact=True``).
* Shapes the kernel cannot serve even when forced — grouped/depthwise
  convs, strided convs on the compiled path (:func:`_mosaic_accepts`),
  blocks that cannot fit scoped VMEM (e.g. the C=3 stem conv, whose
  lane-padded image block alone overflows) — run XLA's conv;
  :class:`PallasConv` counts every site and why it runs XLA's conv in
  :func:`conv_dispatch_stats`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pallas_compat import align_vma as _align_vma
from ..pallas_compat import match_vma as _match_vma
from ..pallas_compat import mxu_dot as _mxu_dot
from ..pallas_compat import sds_with_vma as _sds
from ..tune import space as _space
from ..tune.dispatch import kernel_config as _tuned_config
from ..normalization.fused_bn_act import _bwd_ref as _ep_bwd_ref
from ..normalization.fused_bn_act import _fwd_ref as _ep_fwd_ref
from ..normalization.fused_bn_act import bn_act_epilogue_ref
from ..normalization.fused_layer_norm import _use_pallas

__all__ = ["conv2d", "conv2d_ref", "PallasConv", "conv_dispatch_stats",
           "reset_conv_dispatch_stats", "publish_conv_counters",
           "tune_bucket"]

#: config-cache version of this kernel's blocking scheme (ISSUE 14).
TUNE_VERSION = 1

#: default im2col row-tile (output rows per block = block_m // OW) and
#: output-channel tile — the zero-cost fallback the tune cache refines.
_DEFAULT_BLOCK_M = 512
_DEFAULT_BLOCK_N = 256

_DN_NHWC = ("NHWC", "HWIO", "NHWC")


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    a, b = v
    return (int(a), int(b))


def _norm_padding(padding, h: int, w: int, kh: int, kw: int,
                  sh: int, sw: int, dh: int, dw: int):
    """Normalize ``padding`` to the hashable ``((pt, pb), (pl, pr))``
    form (flax conventions: ``"SAME"``/``"VALID"``, an int, a pair of
    ints, or explicit per-dim pairs)."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return ((0, 0), (0, 0))
        if p == "SAME":
            def same(sz, k, s, d):
                out = -(-sz // s)
                total = max(0, (out - 1) * s + (k - 1) * d + 1 - sz)
                return (total // 2, total - total // 2)
            return (same(h, kh, sh, dh), same(w, kw, sw, dw))
        raise ValueError(f"padding must be 'SAME'/'VALID' or explicit "
                         f"pairs; got {padding!r}")
    if isinstance(padding, int):
        return ((padding, padding), (padding, padding))
    pads = tuple(padding)
    if len(pads) == 2 and all(isinstance(p, int) for p in pads):
        return ((pads[0], pads[0]), (pads[1], pads[1]))
    return tuple((int(a), int(b)) for a, b in pads)


def _out_hw(h: int, w: int, padding, kh: int, kw: int, sh: int, sw: int,
            dh: int, dw: int) -> Tuple[int, int]:
    (pt, pb), (pl_, pr) = padding
    oh = (h + pt + pb - (kh - 1) * dh - 1) // sh + 1
    ow = (w + pl_ + pr - (kw - 1) * dw - 1) // sw + 1
    return oh, ow


def _pick_block(total: int, block: int, unit: int) -> int:
    """Block size capped at ``block``, rounded to a ``unit`` multiple
    where the extent allows it (the quant.kernels rule)."""
    b = min(block, max(unit, (total + unit - 1) // unit * unit))
    return min(b, total) if total >= unit else total


def _pick_boh(oh: int, ow: int, block_m: int) -> int:
    """Output rows per block: the im2col row-tile ``block_m`` divided by
    the row width ``OW``, floored at one output row."""
    return max(1, min(oh, block_m // max(1, ow)))


def _pad_up(v: int, m: int) -> int:
    return -(-v // m) * m


# -- VMEM sizing (the tune/space model, 4-D conv edition) ---------------------
#
# Blocks are tiled on their LAST TWO dims ((8, 128) fp32 granularity),
# so the estimate lane-pads the channel axis and sublane-pads the axis
# before it — the C=3 stem conv pays for 128 lanes whether it uses them
# or not, which is exactly why it must fall back.

def _fwd_vmem_bytes(hp: int, wp: int, c: int, kh: int, kw: int, boh: int,
                    ow: int, bo: int, isz: int, has_z: bool,
                    want_preact: bool) -> int:
    x_b = hp * _pad_up(wp, 8) * _pad_up(c, 128) * isz
    w_b = kh * kw * _pad_up(c, 8) * _pad_up(bo, 128) * isz
    acc_b = _pad_up(boh * ow, 8) * _pad_up(bo, 128) * 4
    out_b = boh * _pad_up(ow, 8) * _pad_up(bo, 128) * isz
    total = x_b + w_b + acc_b + out_b
    if has_z:
        total += out_b
    if want_preact:
        total += out_b
    return total


def _fwd_fits(h: int, w: int, padding, c: int, o: int, kh: int, kw: int,
              sh: int, sw: int, dh: int, dw: int, block_m: int,
              block_n: int, isz: int, has_z: bool,
              want_preact: bool) -> bool:
    oh, ow = _out_hw(h, w, padding, kh, kw, sh, sw, dh, dw)
    if oh < 1 or ow < 1:
        return False
    boh = _pick_boh(oh, ow, block_m)
    bo = _pick_block(o, block_n, 128)
    nbh = -(-oh // boh)
    hp = (nbh * boh - 1) * sh + (kh - 1) * dh + (boh - 1) * sh + 1
    wp = (ow - 1) * sw + (kw - 1) * dw + 1
    return _fwd_vmem_bytes(hp, wp, c, kh, kw, boh, ow, bo, isz, has_z,
                           want_preact) <= _space.VMEM_BUDGET_BYTES


def _dgrad_fits(h: int, w: int, oh: int, ow: int, c: int, o: int, kh: int,
                kw: int, sh: int, sw: int, dh: int, dw: int, block_m: int,
                block_n: int, isz: int) -> bool:
    # dgrad is the forward machinery on the stride-dilated cotangent
    # [N, ~H + (KH-1)dh, ~W + (KW-1)dw, O] producing [N, H, W, C]
    hg = (oh - 1) * sh + 1 + (kh - 1) * dh
    wg = (ow - 1) * sw + 1 + (kw - 1) * dw
    boh = _pick_boh(h, w, block_m)
    bc = _pick_block(c, block_n, 128)
    nbh = -(-h // boh)
    hp = nbh * boh + (kh - 1) * dh
    return _fwd_vmem_bytes(max(hp, hg), max(w + (kw - 1) * dw, wg), o,
                           kh, kw, boh, w, bc, isz, False,
                           False) <= _space.VMEM_BUDGET_BYTES


def _wgrad_fits(h: int, w: int, padding, oh: int, ow: int, c: int, o: int,
                kh: int, kw: int, block_n: int, isz: int) -> bool:
    (pt, pb), (pl_, pr) = padding
    hp, wp = h + pt + pb, w + pl_ + pr
    bo = _pick_block(o, block_n, 128)
    x_b = hp * _pad_up(wp, 8) * _pad_up(c, 128) * isz
    g_b = oh * _pad_up(ow, 8) * _pad_up(bo, 128) * isz
    dw_b = kh * kw * _pad_up(c, 8) * _pad_up(bo, 128) * 4
    tmp = _pad_up(oh * ow, 8) * (_pad_up(c, 128) + _pad_up(bo, 128)) * 4
    return x_b + g_b + dw_b + tmp <= _space.VMEM_BUDGET_BYTES


def tune_bucket(n: int, oh: int, ow: int, c: int, o: int, kh: int, kw: int,
                sh: int, sw: int, dh: int, dw: int, isz: int,
                epilogue: bool, has_z: bool) -> str:
    """Config-cache shape bucket: batch and the joint output spatial
    extent round to powers of two (:func:`apex_tpu.tune.space.
    nhwc_bucket` — the block sweep tiles ``OH*OW`` rows, so ``56x56``
    and ``64x49`` share a winner); channels, the filter/stride/dilation
    geometry, itemsize, and the epilogue/residual flags (extra VMEM
    residents per block) are exact."""
    return (f"{_space.nhwc_bucket(n, oh, ow, c)}_o{o}_k{kh}x{kw}"
            f"_s{sh}x{sw}_d{dh}x{dw}_i{isz}_e{int(epilogue)}"
            f"_z{int(has_z)}")


# -- reference math (jnp fallback + oracle) -----------------------------------

def _raw_conv(x, w, stride, padding, dilation, groups, out_dtype):
    # fp32 accumulation via explicit upcast, not preferred_element_type:
    # the conv transpose rule rejects an fp32 cotangent against bf16
    # operands, so a preferred_element_type reference would not be
    # differentiable in low precision — astype transposes cleanly.
    return jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=stride, padding=padding, rhs_dilation=dilation,
        dimension_numbers=_DN_NHWC,
        feature_group_count=groups).astype(out_dtype)


def conv2d_ref(x, w, *, stride=(1, 1), padding="SAME", dilation=(1, 1),
               groups=1, mean=None, invstd=None, scale=None, bias=None,
               z=None, relu=False):
    """jnp reference: NHWC ``lax.conv_general_dilated`` (fp32
    accumulation, cast back) followed by the
    :func:`~apex_tpu.normalization.fused_bn_act.bn_act_epilogue_ref`
    epilogue when ``mean``/``invstd`` are given — the CPU fallback and
    the correctness oracle for the Pallas kernels."""
    stride, dilation = _pair(stride), _pair(dilation)
    padding = _norm_padding(padding, x.shape[1], x.shape[2], w.shape[0],
                            w.shape[1], *stride, *dilation)
    y = _raw_conv(x, w, stride, padding, dilation, groups,
                  jnp.result_type(x, w))
    if mean is None:
        return y
    return bn_act_epilogue_ref(y, mean, invstd, scale, bias, z, relu)


# -- pallas kernels -----------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, mean_ref, invstd_ref, s_ref, b_ref, z_ref,
                *out_refs, kh, kw, sh, sw, dh, dw, ow, epilogue, affine,
                has_z, relu, want_preact):
    out_ref = out_refs[0]
    _, boh, _, bo = out_ref.shape
    c = x_ref.shape[3]
    i = pl.program_id(2)
    row0 = i * boh * sh
    span = (boh - 1) * sh + 1
    acc = jnp.zeros((boh * ow, bo), jnp.float32)
    for ikh in range(kh):            # static tap loop: KH*KW shifted
        for ikw in range(kw):        # strided slices + MXU matmuls
            xs = x_ref[0, pl.ds(row0 + ikh * dh, span), :, :]
            xs = xs[::sh, ikw * dw: ikw * dw + (ow - 1) * sw + 1: sw, :]
            acc = acc + _mxu_dot(xs.reshape(boh * ow, c),
                                 w_ref[ikh, ikw], ((1,), (0,)))
    res = acc.astype(out_ref.dtype)
    if want_preact:
        out_refs[1][0] = res.reshape(boh, ow, bo)
    if epilogue:
        # Same cast sequence as the explicit chain (conv result cast to
        # the activation dtype, epilogue re-upcasts) so fused == chain
        # bitwise, not merely to tolerance.
        of = (res.astype(jnp.float32) - mean_ref[:]) * invstd_ref[:]
        if affine:
            of = of * s_ref[:] + b_ref[:]
        if has_z:
            of = of + z_ref[0].reshape(boh * ow, bo).astype(jnp.float32)
        if relu:
            of = jnp.maximum(of, 0.0)
        res = of.astype(out_ref.dtype)
    out_ref[0] = res.reshape(boh, ow, bo)


def _vec(v, o):
    return jnp.reshape(jnp.asarray(v, jnp.float32), (1, o))


def _im2col_conv(xp, w, stride, dilation, oh, ow, mean, invstd, scale,
                 bias, z, relu, want_preact, blocks, interpret, out_dtype):
    """The forward pallas_call on an already conv-padded input ``xp``
    (used directly by the forward, and by dgrad on the stride-dilated
    cotangent with rotated weights)."""
    n, hp, wp, c = xp.shape
    kh, kw, _, o = w.shape
    sh, sw = stride
    dh, dw = dilation
    bm = blocks[0] or _DEFAULT_BLOCK_M
    bo = _pick_block(o, blocks[1] or _DEFAULT_BLOCK_N, 128)
    boh = _pick_boh(oh, ow, bm)
    nbh = -(-oh // boh)
    nbo = -(-o // bo)
    # Alignment padding: the last oh-block's taps read past the conv
    # extent; grow the zero margin so no in-kernel slice is ever
    # clamped (clamping would SHIFT the slice and corrupt the final
    # block's in-bounds rows, not just the masked tail).
    hp_need = ((nbh * boh - 1) * sh + (kh - 1) * dh + (boh - 1) * sh + 1)
    wp_need = (ow - 1) * sw + (kw - 1) * dw + 1
    if hp < hp_need or wp < wp_need:
        xp = jnp.pad(xp, ((0, 0), (0, max(0, hp_need - hp)),
                          (0, max(0, wp_need - wp)), (0, 0)))
        hp, wp = xp.shape[1], xp.shape[2]
    epilogue = mean is not None
    affine = scale is not None
    has_z = z is not None
    mean2 = _vec(mean if epilogue else jnp.zeros((o,)), o)
    invstd2 = _vec(invstd if epilogue else jnp.zeros((o,)), o)
    s2 = _vec(scale if affine else jnp.zeros((o,)), o)
    b2 = _vec(bias if affine else jnp.zeros((o,)), o)
    zz = z if has_z else jnp.zeros((1, 1, 1, o), out_dtype)
    vec = pl.BlockSpec((1, bo), lambda b, j, i: (0, j))
    x_spec = pl.BlockSpec((1, hp, wp, c), lambda b, j, i: (b, 0, 0, 0))
    w_spec = pl.BlockSpec((kh, kw, c, bo), lambda b, j, i: (0, 0, 0, j))
    out_spec = pl.BlockSpec((1, boh, ow, bo), lambda b, j, i: (b, i, 0, j))
    z_spec = out_spec if has_z else pl.BlockSpec(
        (1, 1, 1, bo), lambda b, j, i: (0, 0, 0, j))
    kernel = functools.partial(_fwd_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                               dh=dh, dw=dw, ow=ow, epilogue=epilogue,
                               affine=affine, has_z=has_z, relu=relu,
                               want_preact=want_preact)
    operands = _align_vma(xp, w, mean2, invstd2, s2, b2, zz)
    out_shape = _sds((n, oh, ow, o), out_dtype, *operands)
    res = pl.pallas_call(
        kernel,
        grid=(n, nbo, nbh),
        in_specs=[x_spec, w_spec, vec, vec, vec, vec, z_spec],
        out_specs=[out_spec, out_spec] if want_preact else out_spec,
        out_shape=[out_shape, out_shape] if want_preact else out_shape,
        interpret=interpret,
    )(*operands)
    if want_preact:
        return res[0], res[1]
    return res, None


def _pallas_fwd(x, w, stride, padding, dilation, mean, invstd, scale,
                bias, z, relu, want_preact, blocks, interpret, out_dtype):
    (pt, pb), (pl_, pr) = padding
    oh, ow = _out_hw(x.shape[1], x.shape[2], padding, w.shape[0],
                     w.shape[1], *stride, *dilation)
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    return _im2col_conv(xp, w, stride, dilation, oh, ow, mean, invstd,
                        scale, bias, z, relu, want_preact, blocks,
                        interpret, out_dtype)


def _pallas_dgrad(dy, w, stride, padding, dilation, hw, blocks, interpret):
    """dx via the forward machinery: stride-dilate the cotangent, pad to
    the 'full' extent, convolve at stride 1 with the spatially rotated,
    in/out-transposed weights."""
    n, oh, ow, o = dy.shape
    kh, kw, c, _ = w.shape
    sh, sw = stride
    dh, dw = dilation
    (pt, pb), (pl_, pr) = padding
    h, w_in = hw
    lo_h, hi_h = (kh - 1) * dh - pt, h + pt - (oh - 1) * sh - 1
    lo_w, hi_w = (kw - 1) * dw - pl_, w_in + pl_ - (ow - 1) * sw - 1
    gd = jax.lax.pad(dy, jnp.zeros((), dy.dtype),
                     ((0, 0, 0), (lo_h, hi_h, sh - 1),
                      (lo_w, hi_w, sw - 1), (0, 0, 0)))
    w_rot = jnp.transpose(w[::-1, ::-1], (0, 1, 3, 2))
    dx, _ = _im2col_conv(gd, w_rot, (1, 1), (dh, dw), h, w_in, None,
                         None, None, None, None, False, False, blocks,
                         interpret, dy.dtype)
    return dx


def _wgrad_kernel(x_ref, g_ref, dw_ref, *, kh, kw, sh, sw, dh, dw, oh, ow):
    b = pl.program_id(1)
    c = x_ref.shape[3]
    bo = g_ref.shape[3]

    @pl.when(b == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    g2 = g_ref[0].reshape(oh * ow, bo)
    xv = x_ref[0]
    for ikh in range(kh):
        for ikw in range(kw):
            xs = xv[ikh * dh: ikh * dh + (oh - 1) * sh + 1: sh,
                    ikw * dw: ikw * dw + (ow - 1) * sw + 1: sw, :]
            t = _mxu_dot(xs.reshape(oh * ow, c), g2, ((0,), (0,)))
            dw_ref[ikh * kw + ikw] = dw_ref[ikh * kw + ikw] + t


def _pallas_wgrad(x, dy, stride, padding, dilation, w_shape, blocks,
                  interpret, w_dtype):
    kh, kw, c, o = w_shape
    sh, sw = stride
    dh, dw = dilation
    n, oh, ow, _ = dy.shape
    (pt, pb), (pl_, pr) = padding
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    bo = _pick_block(o, blocks[1] or _DEFAULT_BLOCK_N, 128)
    nbo = -(-o // bo)
    kernel = functools.partial(_wgrad_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                               dh=dh, dw=dw, oh=oh, ow=ow)
    operands = _align_vma(xp, dy)
    dwf = pl.pallas_call(
        kernel,
        grid=(nbo, n),     # n innermost: the dw block stays resident
        in_specs=[pl.BlockSpec((1, hp, wp, c), lambda j, b: (b, 0, 0, 0)),
                  pl.BlockSpec((1, oh, ow, bo), lambda j, b: (b, 0, 0, j))],
        out_specs=pl.BlockSpec((kh * kw, c, bo), lambda j, b: (0, 0, j)),
        out_shape=_sds((kh * kw, c, o), jnp.float32, *operands),
        interpret=interpret,
    )(*operands)
    return dwf.reshape(kh, kw, c, o).astype(w_dtype)


# -- custom VJP ---------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12,
                                                    13, 14))
def _conv(x, w, mean, invstd, scale, bias, z, groups, relu, stride,
          padding, dilation, use_pallas, interpret, blocks):
    if use_pallas:
        out, _ = _pallas_fwd(x, w, stride, padding, dilation, mean,
                             invstd, scale, bias, z, relu, False, blocks,
                             interpret, x.dtype)
        return out
    y = _raw_conv(x, w, stride, padding, dilation, groups, x.dtype)
    if mean is None:
        return y
    return _ep_fwd_ref(y, mean, invstd, scale, bias, z, relu)


def _conv_fwd(x, w, mean, invstd, scale, bias, z, groups, relu, stride,
              padding, dilation, use_pallas, interpret, blocks):
    epilogue = mean is not None
    if use_pallas:
        out, y = _pallas_fwd(x, w, stride, padding, dilation, mean,
                             invstd, scale, bias, z, relu, epilogue,
                             blocks, interpret, x.dtype)
    else:
        y = _raw_conv(x, w, stride, padding, dilation, groups, x.dtype)
        out = (_ep_fwd_ref(y, mean, invstd, scale, bias, z, relu)
               if epilogue else y)
    # the pre-activation is a residual only when the epilogue consumed
    # it (its ReLU mask + per-channel cotangents); a plain conv's
    # backward needs only (x, w).
    return out, (x, w, mean, invstd, scale, bias, z,
                 y if epilogue else None)


def _conv_bwd(groups, relu, stride, padding, dilation, use_pallas,
              interpret, blocks, res, g):
    x, w, mean, invstd, scale, bias, z, y = res
    epilogue = mean is not None
    if epilogue:
        # fused_bn_act's reference backward on the saved pre-activation:
        # dy (activation-sized, ReLU-masked) in one shot plus the
        # per-channel column sums — gradient-exact vs the explicit
        # conv -> bn_relu_residual chain by construction.
        dy, d_mean, d_invstd, d_scale, d_bias, dz = _ep_bwd_ref(
            g, y, mean, invstd, scale, bias, z, relu)
    else:
        dy, d_mean, d_invstd, d_scale, d_bias, dz = (g, None, None,
                                                     None, None, None)
    n, h, w_in, c = x.shape
    kh, kw, _, o = w.shape
    oh, ow = dy.shape[1], dy.shape[2]
    isz = jnp.dtype(x.dtype).itemsize
    bm = blocks[0] or _DEFAULT_BLOCK_M
    bn = blocks[1] or _DEFAULT_BLOCK_N
    pallas_dx = use_pallas and _dgrad_fits(
        h, w_in, oh, ow, c, o, kh, kw, *stride, *dilation, bm, bn, isz)
    pallas_dw = use_pallas and _wgrad_fits(
        h, w_in, padding, oh, ow, c, o, kh, kw, bn, isz)
    jdx = jdw = None
    if not (pallas_dx and pallas_dw):
        _, vjp = jax.vjp(
            lambda xx, ww: _raw_conv(xx, ww, stride, padding, dilation,
                                     groups, x.dtype), x, w)
        jdx, jdw = vjp(dy)
    dx = (_pallas_dgrad(dy, w, stride, padding, dilation, (h, w_in),
                        blocks, interpret) if pallas_dx else jdx)
    dw = (_pallas_wgrad(x, dy, stride, padding, dilation, w.shape,
                        blocks, interpret, w.dtype) if pallas_dw else jdw)
    # The weight and the per-channel epilogue operands are usually
    # replicated over a data axis the activations are sharded on: the
    # kernels' dw and the column sums are per-shard here and must
    # arrive summed (see pallas_compat.match_vma; jax.vjp of the XLA
    # conv already returns its dw summed, for which this is a no-op).
    return tuple(_match_vma(ct, p) for ct, p in zip(
        (dx.astype(x.dtype), dw, d_mean, d_invstd, d_scale, d_bias, dz),
        res))


_conv.defvjp(_conv_fwd, _conv_bwd)


# -- dispatch + public op -----------------------------------------------------

def _mosaic_accepts(stride) -> bool:
    """What the Mosaic compiler takes of this kernel family (measured on
    the v5e, jax 0.9.0 / libtpu 0.0.34, PR 21): every stride-1 ResNet-50
    site compiles and matches the reference; a stride-2 tap is a strided
    *value* slice (``xs[::sh, a:b:sw, :]``), which the Pallas TPU
    lowering turns into a gather and refuses — "NotImplementedError:
    Only 2D gather is supported".  Strided sites therefore take the XLA
    path on the chip (the interpreter still runs them, for the tests)."""
    return tuple(stride) == (1, 1)


def _dispatch_pallas(impl: Optional[str], fits: bool) -> bool:
    """True when the Mosaic kernels are what runs.

    The automatic choice (``impl=None``) is XLA's conv at every shape:
    inside the ResNet-50 amp-O2 step on the v5e no site's kernel won
    (``PERF.md`` section 6, PR 26).  A class of shapes a later
    measurement earns goes here, as a predicate on the site's shape."""
    if impl not in (None, "pallas", "jnp"):
        raise ValueError(
            f"impl must be None, 'pallas', or 'jnp'; got {impl!r}")
    return impl == "pallas" and fits and _use_pallas()


def conv2d(x, w, *, stride=(1, 1), padding="SAME", dilation=(1, 1),
           groups: int = 1, mean=None, invstd=None, scale=None, bias=None,
           z=None, relu: bool = False, impl: Optional[str] = None,
           interpret: bool = False, block_m: Optional[int] = None,
           block_n: Optional[int] = None):
    """NHWC 2-D convolution with an optional fused BN/ReLU/residual
    epilogue: ``relu((conv(x, w) - mean) * invstd * scale + bias + z)``.

    ``x``: ``[N, H, W, C]``; ``w``: ``[KH, KW, C // groups, O]`` (the
    flax/``lax.conv_general_dilated`` HWIO layout).  ``stride``/
    ``dilation`` are ints or pairs; ``padding`` is ``"SAME"``,
    ``"VALID"``, an int, or explicit ``((pt, pb), (pl, pr))`` pairs.
    Accumulation is fp32; the result is cast to the operands' dtype.

    The epilogue (active when ``mean``/``invstd`` are given) is the
    :func:`~apex_tpu.normalization.bn_relu_residual` contract with the
    conv output as its input — per-channel fp32 ``mean``/``invstd`` and
    optional affine ``scale``/``bias``, an optional residual ``z`` of
    the output's shape added before the ReLU — fused into the conv
    kernel's epilogue so the chain costs one HBM round-trip per block.
    All epilogue operands are differentiable; statistics computed
    outside (XLA reductions / SyncBatchNorm psums) receive exact
    cotangents, and the fused path is gradient-exact vs the explicit
    ``conv2d`` → ``bn_relu_residual`` chain.

    ``impl``: ``None`` and ``"jnp"`` run XLA's conv (see the module
    docstring for the measurement); ``"pallas"`` forces the kernels on
    the TPU where they can serve the shape (``groups == 1``, stride 1,
    blocks that fit scoped VMEM).  ``interpret=True`` runs the real
    kernels in interpreter mode (CPU tier-parity tests).  ``block_m``
    (im2col row tile) / ``block_n`` (output-channel tile): explicit
    kernel blocks; left ``None`` the per-device config cache
    (:mod:`apex_tpu.tune`) is consulted at trace time with the
    hard-coded defaults as zero-cost fallback.
    """
    stride, dilation = _pair(stride), _pair(dilation)
    if x.ndim != 4 or w.ndim != 4:
        raise ValueError(f"conv2d wants NHWC x and HWIO w; got "
                         f"{x.shape} / {w.shape}")
    n, h, w_in, cin = x.shape
    kh, kw, wc, o = w.shape
    if wc * groups != cin:
        raise ValueError(f"w in-channels {wc} x groups {groups} != input "
                         f"channels {cin}")
    if (mean is None) != (invstd is None):
        raise ValueError("mean and invstd must be given together")
    if mean is None and (scale is not None or z is not None or relu):
        raise ValueError("scale/bias, z and relu belong to the fused "
                         "epilogue — pass mean/invstd to enable it")
    if (scale is None) != (bias is None):
        raise ValueError("scale and bias must be given together")
    dt = jnp.result_type(x, w)
    x = x.astype(dt)
    w = w.astype(dt)
    padding = _norm_padding(padding, h, w_in, kh, kw, *stride, *dilation)
    oh, ow = _out_hw(h, w_in, padding, kh, kw, *stride, *dilation)
    epilogue = mean is not None
    if epilogue:
        mean = jnp.ravel(jnp.asarray(mean, jnp.float32))
        invstd = jnp.ravel(jnp.asarray(invstd, jnp.float32))
        if scale is not None:
            scale = jnp.ravel(jnp.asarray(scale, jnp.float32))
            bias = jnp.ravel(jnp.asarray(bias, jnp.float32))
        if z is not None:
            if z.shape != (n, oh, ow, o):
                raise ValueError(f"z must have the output shape "
                                 f"{(n, oh, ow, o)}; got {z.shape}")
            z = z.astype(dt)
    isz = jnp.dtype(dt).itemsize
    capable = groups == 1
    fits = capable and _mosaic_accepts(stride) and _fwd_fits(
        h, w_in, padding, cin, o, kh, kw, *stride, *dilation,
        block_m or _DEFAULT_BLOCK_M, block_n or _DEFAULT_BLOCK_N, isz,
        z is not None, epilogue)
    use_pallas = _dispatch_pallas(impl, fits)
    if interpret and impl != "jnp" and capable:
        use_pallas = True
    if use_pallas and block_m is None and block_n is None:
        cfg = _tuned_config(
            "conv2d", TUNE_VERSION,
            tune_bucket(n, oh, ow, cin, o, kh, kw, *stride, *dilation,
                        isz, epilogue, z is not None),
            params=("block_m", "block_n"))
        if cfg and _fwd_fits(h, w_in, padding, cin, o, kh, kw, *stride,
                             *dilation, cfg["block_m"], cfg["block_n"],
                             isz, z is not None, epilogue):
            block_m, block_n = cfg["block_m"], cfg["block_n"]
    return _conv(x, w, mean, invstd, scale, bias, z, int(groups),
                 bool(relu), stride, padding, dilation, use_pallas,
                 bool(interpret), (block_m, block_n))


# -- flax module + per-site dispatch stats ------------------------------------

_DISPATCH_COUNTS: Dict[str, int] = {"pallas": 0, "fallback": 0}
_FALLBACK_REASONS: Dict[str, int] = {}


def conv_dispatch_stats() -> Dict[str, Any]:
    """Trace-time :class:`PallasConv` dispatch counters: how many conv
    call sites traced the Pallas kernel vs ran XLA's conv, and why
    (``groups`` / ``rank`` / ``backend`` / ``stride`` / ``vmem``: the
    kernel cannot serve the site; ``xla``: it can, and the automatic
    dispatch takes XLA's conv).  A site counts as pallas only when the
    kernel IS what was traced — off the TPU every site is a ``backend``
    fallback.
    Counts accumulate per trace (init, apply, and grad traces each count
    their sites)."""
    return {"pallas_sites": _DISPATCH_COUNTS["pallas"],
            "fallback_sites": _DISPATCH_COUNTS["fallback"],
            "fallback_reasons": dict(_FALLBACK_REASONS)}


def reset_conv_dispatch_stats() -> None:
    _DISPATCH_COUNTS["pallas"] = _DISPATCH_COUNTS["fallback"] = 0
    _FALLBACK_REASONS.clear()


def publish_conv_counters(registry) -> Dict[str, int]:
    """Export the dispatch counters into a telemetry
    :class:`~apex_tpu.telemetry.MetricsRegistry` as monotonic
    ``conv_pallas_sites`` / ``conv_fallback_sites`` /
    ``conv_fallback_<reason>`` counters (ISSUE 20 satellite: the dark
    counts, on the Prometheus surface instead of only a stats dict).

    Delta-published — each call bumps every counter by how much its
    module-global count grew since the LAST publish, so periodic calls
    (an exporter hook, an example's exit path) stay monotonic even
    though :func:`reset_conv_dispatch_stats` may never run.  Returns
    the raw stats dict for the caller's own print line."""
    stats = conv_dispatch_stats()
    flat: Dict[str, int] = {
        "conv_pallas_sites": stats["pallas_sites"],
        "conv_fallback_sites": stats["fallback_sites"],
    }
    for reason, n in stats["fallback_reasons"].items():
        flat[f"conv_fallback_{reason}"] = int(n)
    for name, total in flat.items():
        c = registry.counter(name)
        delta = total - (c.value or 0)
        if delta > 0:
            c.inc(delta)
    return stats


def _site_reason(x_shape, w_shape, padding, stride, dilation,
                 groups: int, isz: int) -> Optional[str]:
    """Why this call site does not trace the kernel (None = it does):
    the same gates, in the same order, as :func:`conv2d`'s automatic
    dispatch."""
    if len(x_shape) != 4:
        return "rank"
    if groups != 1:
        return "groups"
    if not _use_pallas():
        return "backend"
    if not _mosaic_accepts(stride):
        return "stride"
    _, h, w_in, cin = x_shape
    kh, kw, _, o = w_shape
    if not _fwd_fits(h, w_in, padding, cin, o, kh, kw, *stride,
                     *dilation, _DEFAULT_BLOCK_M, _DEFAULT_BLOCK_N, isz,
                     False, False):
        return "vmem"
    if not _dispatch_pallas(None, True):
        return "xla"
    return None


class PallasConv(nn.Module):
    """Drop-in ``nn.Conv`` stand-in routing through :func:`conv2d`.

    Same parameter pytree as ``nn.Conv`` (an HWIO ``kernel`` plus an
    optional ``bias``, identical initializers), so swapping it in via
    the ResNet ``conv_cls=`` hook changes no checkpoint or init — with
    the flag off (``conv_cls=None`` → ``nn.Conv``) the model is
    bit-identical to before.  Every call site the automatic dispatch
    does not give the kernel (today: all of them, see
    :func:`_dispatch_pallas`) runs exactly the XLA conv ``nn.Conv``
    runs, in the operands' dtype, and is counted with its reason in
    :func:`conv_dispatch_stats`.  ``precision`` is accepted for
    signature parity but ignored (the kernel always accumulates fp32).
    """
    features: int
    kernel_size: Sequence[int]
    strides: Union[None, int, Sequence[int]] = 1
    padding: Any = "SAME"
    kernel_dilation: Union[None, int, Sequence[int]] = 1
    feature_group_count: int = 1
    use_bias: bool = True
    dtype: Any = None
    param_dtype: Any = jnp.float32
    precision: Any = None
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros

    @nn.compact
    def __call__(self, x):
        kh, kw = (self.kernel_size if not isinstance(self.kernel_size, int)
                  else (self.kernel_size, self.kernel_size))
        groups = self.feature_group_count
        cin = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init,
                            (kh, kw, cin // groups, self.features),
                            self.param_dtype)
        bias = (self.param("bias", self.bias_init, (self.features,),
                           self.param_dtype) if self.use_bias else None)
        if self.dtype is not None:
            x = x.astype(self.dtype)
            kernel = kernel.astype(self.dtype)
            bias = bias.astype(self.dtype) if bias is not None else None
        stride = _pair(self.strides if self.strides is not None else 1)
        dilation = _pair(self.kernel_dilation
                         if self.kernel_dilation is not None else 1)
        padding = _norm_padding(self.padding, x.shape[1], x.shape[2],
                                kh, kw, *stride, *dilation)
        isz = jnp.dtype(jnp.result_type(x, kernel)).itemsize
        reason = _site_reason(x.shape, kernel.shape, padding, stride,
                              dilation, groups, isz)
        if reason is None:
            _DISPATCH_COUNTS["pallas"] += 1
            y = conv2d(x, kernel, stride=stride, padding=padding,
                       dilation=dilation)
        else:
            _DISPATCH_COUNTS["fallback"] += 1
            _FALLBACK_REASONS[reason] = _FALLBACK_REASONS.get(reason,
                                                              0) + 1
            y = jax.lax.conv_general_dilated(
                x, kernel, window_strides=stride, padding=padding,
                rhs_dilation=dilation, dimension_numbers=_DN_NHWC,
                feature_group_count=groups)
        if bias is not None:
            y = y + jnp.reshape(bias, (1, 1, 1, -1))
        return y
