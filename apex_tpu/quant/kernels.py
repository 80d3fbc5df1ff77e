"""Quantized matmul kernels: Pallas quantize → int8×int8→int32 →
dequantize-fused epilogue (ISSUE 13 tentpole, layer 1).

The amp pillar's bf16 matmuls stream 2 bytes/element through the MXU;
int8 halves that again and the MXU's int8 path doubles the MAC rate —
the "next 2x after bf16" the ROADMAP names.  The numerics recipe is
LLM.int8()-style symmetric absmax scaling (Dettmers et al.):

* **activations** quantize per-tensor against a FROZEN calibration
  scale (``apex_tpu.quant.calibrate`` harvests absmax over an
  observation phase; recomputing ``abs().max()`` per step is the
  anti-pattern jaxlint J014 flags);
* **weights** quantize per-channel (one scale per output column) from
  their CURRENT values — weights are known exactly at trace time, so
  per-step channel scales cost one cheap reduction and track training;
* the kernel quantizes the activation block in VMEM, runs the
  int8×int8→int32 dot on the MXU, and applies the dequantize epilogue
  (``acc * x_scale * w_scale[n]``) before the store — ONE pass over the
  activation bytes, no materialized int8 copy in HBM;
* the **backward stays in bf16** via a custom VJP (the straight-through
  estimator): ``dx = g @ w.T``, ``dw = x.T @ g`` on the saved
  full-precision operands — the same pattern as
  ``normalization/fused_bn_act.py`` and contrib xentropy, including the
  jnp reference that doubles as CPU fallback + test oracle and
  ``interpret=True`` running the REAL kernel in CPU tests.

Scale convention: ``dequant(q) = q * scale`` with ``scale = amax / 127``
(symmetric, no zero point).  A zero-amax channel (an all-zero weight
column) gets scale 1.0 so it quantizes to — and dequantizes from —
exact zeros instead of dividing by zero.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..normalization.fused_layer_norm import _use_pallas
from ..pallas_compat import align_vma as _align_vma
from ..pallas_compat import match_vma as _match_vma
from ..pallas_compat import mxu_dot as _mxu_dot
from ..pallas_compat import sds_with_vma as _sds
from ..tune.dispatch import kernel_config as _tuned_config
from ..tune.space import pow2_bucket as _pow2

__all__ = ["amax_to_scale", "quantize", "dequantize", "channel_scale",
           "quantized_matmul", "quantized_matmul_ref", "saturation_count",
           "QMAX"]

#: config-cache version of this kernel's blocking scheme (ISSUE 14).
TUNE_VERSION = 1

#: symmetric int8 range: quantized values live in [-QMAX, QMAX].
QMAX = 127.0


def amax_to_scale(amax):
    """``scale = amax / 127`` with the zero-amax guard (scale 1.0 for
    all-zero tensors/channels, so they round-trip as exact zeros)."""
    amax = jnp.asarray(amax, jnp.float32)
    return jnp.where(amax > 0, amax / QMAX, jnp.float32(1.0))


def channel_scale(w):
    """Per-output-channel scales ``[N]`` for a ``[K, N]`` weight matrix:
    absmax over each column, through :func:`amax_to_scale`."""
    return amax_to_scale(jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0))


def quantize(x, scale):
    """Symmetric int8 quantization: ``clip(round(x / scale), ±127)``.
    ``scale`` must broadcast against ``x`` (scalar per-tensor, or a
    per-channel vector pre-shaped by the caller).  Round-to-nearest-even
    (``jnp.round``) in fp32 — the ONE rounding definition the Pallas
    kernel, the jnp reference, and the KV-cache path all share."""
    q = jnp.round(x.astype(jnp.float32) * (1.0 / scale))
    return jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


def dequantize(q, scale, dtype=jnp.float32):
    """``q * scale`` back to ``dtype``."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def saturation_count(x, x_scale):
    """Elements of ``x`` whose magnitude exceeds the calibrated range
    ``127 * x_scale`` — they clip under :func:`quantize`.  A device-side
    int32 scalar; feed the fetched value to
    :meth:`apex_tpu.quant.calibrate.Calibration.note_saturation` so the
    ``quant_scale_saturation`` watchdog rule sees it."""
    limit = QMAX * jnp.asarray(x_scale, jnp.float32)
    return jnp.sum(jnp.abs(x.astype(jnp.float32)) > limit).astype(jnp.int32)


# -- reference math (jnp fallback + oracle) -----------------------------------

def _matmul_ref(x2d, qw, x_scale, w_scale, out_dtype):
    """The jnp reference: same quantize / int8-dot / dequant ops as the
    kernel, so interpret-mode parity is exact."""
    qx = quantize(x2d, x_scale)
    acc = jax.lax.dot_general(qx, qw, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (x_scale * w_scale)[None, :]
    return out.astype(out_dtype)


def quantized_matmul_ref(x, w, *, x_scale, w_scale=None):
    """Public jnp reference of :func:`quantized_matmul` (the test
    oracle): quantize both operands, int8×int8→int32, dequantize."""
    if w_scale is None:
        w_scale = channel_scale(w)
    x_scale = jnp.asarray(x_scale, jnp.float32)
    w_scale = jnp.asarray(w_scale, jnp.float32)
    qw = quantize(w, w_scale[None, :])
    lead = x.shape[:-1]
    out = _matmul_ref(x.reshape(-1, x.shape[-1]), qw, x_scale, w_scale,
                      x.dtype)
    return out.reshape(*lead, w.shape[-1])


# -- pallas kernel ------------------------------------------------------------
#
# Grid over (M blocks, N blocks), full K per block: the quantize of the
# x block, the int8 dot, and the dequant epilogue all happen in VMEM in
# one grid step.  Projection Ks in the model family (<= a few thousand)
# fit comfortably; _kernel_fits gates the rest back to the jnp path.

_BLOCK_M = 256
_BLOCK_N = 256
_QMM_VMEM_BUDGET = 8 * 1024 * 1024


def _pick_block(total: int, block: int, unit: int) -> int:
    b = min(block, max(unit, (total + unit - 1) // unit * unit))
    return min(b, total) if total >= unit else total


def _kernel_fits(bm: int, bn: int, k: int, x_itemsize: int) -> bool:
    # x block + qx int8 + w int8 block + f32 acc/out (+ slack already in
    # the budget)
    need = bm * k * (x_itemsize + 1) + k * bn + 2 * bm * bn * 4
    return need <= _QMM_VMEM_BUDGET


def _qmm_kernel(x_ref, qw_ref, xs_ref, ws_ref, out_ref):
    # quantize the activation block in VMEM (fp32 math, RTNE — identical
    # ops to quantize())
    xs = xs_ref[0, 0]                                   # scalar x_scale
    q = jnp.round(x_ref[:].astype(jnp.float32) * (1.0 / xs))
    qx = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)
    acc = _mxu_dot(qx, qw_ref[:], ((1,), (0,)), jnp.int32)
    # dequantize-fused epilogue: per-channel scale broadcast over rows
    out = acc.astype(jnp.float32) * (xs * ws_ref[:])    # [1, bn] bcast
    out_ref[:] = out.astype(out_ref.dtype)


def tune_bucket(m: int, k: int, n: int, x_itemsize: int) -> str:
    """Config-cache shape bucket: K/N exact (they set the VMEM math),
    rows rounded to a power of two."""
    return f"m{_pow2(m)}_k{k}_n{n}_i{x_itemsize}"


def _pallas_qmm(x2d, qw, x_scale, w_scale, out_dtype, interpret,
                block_m=None, block_n=None):
    m, k = x2d.shape
    n = qw.shape[1]
    bm = _pick_block(m, block_m or _BLOCK_M, 8)
    bn = _pick_block(n, block_n or _BLOCK_N, 128)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    xs2d = jnp.reshape(x_scale.astype(jnp.float32), (1, 1))
    ws2d = jnp.reshape(w_scale.astype(jnp.float32), (1, n))
    operands = _align_vma(x2d, qw, xs2d, ws2d)
    return pl.pallas_call(
        _qmm_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j)),
                  pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=_sds((m, n), out_dtype, *operands),
        interpret=interpret,
    )(*operands)


# -- dispatch -----------------------------------------------------------------

# Below this the custom-call boundary costs more than the int8 saving
# (the fused_layer_norm crossover lesson); benchmark-shape projections
# (batch*seq x hidden) sit far above.
_JNP_MAX_ELEMENTS = 1 * 1024 * 1024


def _dispatch_pallas(m: int, k: int, n: int, impl: Optional[str],
                     x_itemsize: int) -> bool:
    if impl not in (None, "pallas", "jnp"):
        raise ValueError(f"impl must be None, 'pallas', or 'jnp'; "
                         f"got {impl!r}")
    bm = _pick_block(m, _BLOCK_M, 8)
    bn = _pick_block(n, _BLOCK_N, 128)
    if not _use_pallas() or not _kernel_fits(bm, bn, k, x_itemsize):
        return False
    if impl is not None:
        return impl == "pallas"
    return m * k >= _JNP_MAX_ELEMENTS


# -- public op with custom VJP ------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _qmm(x2d, w2d, x_scale, w_scale, use_pallas, interpret, block_m,
         block_n):
    qw = quantize(w2d, w_scale[None, :])
    if use_pallas:
        return _pallas_qmm(x2d, qw, x_scale, w_scale, x2d.dtype, interpret,
                           block_m, block_n)
    return _matmul_ref(x2d, qw, x_scale, w_scale, x2d.dtype)


def _qmm_fwd(x2d, w2d, x_scale, w_scale, use_pallas, interpret, block_m,
             block_n):
    out = _qmm(x2d, w2d, x_scale, w_scale, use_pallas, interpret, block_m,
               block_n)
    return out, (x2d, w2d, x_scale, w_scale)


def _qmm_bwd(use_pallas, interpret, block_m, block_n, res, g):
    # Straight-through backward in the operands' own (bf16) precision:
    # the quantization is treated as identity, so gradients see the
    # full-precision matmul — the LLM.int8()/FP8-training recipe.  The
    # int8 path never appears in the backward program.
    x2d, w2d, x_scale, w_scale = res
    gx = g.astype(x2d.dtype)
    dx = jnp.dot(gx, w2d.T.astype(x2d.dtype)).astype(x2d.dtype)
    dw = jnp.dot(x2d.T.astype(w2d.dtype),
                 g.astype(w2d.dtype)).astype(w2d.dtype)
    # a replicated weight under a sharded batch: dw is per-shard here
    # and must arrive summed (see pallas_compat.match_vma)
    return tuple(_match_vma(ct, p) for ct, p in zip(
        (dx, dw, jnp.zeros_like(x_scale), jnp.zeros_like(w_scale)), res))


_qmm.defvjp(_qmm_fwd, _qmm_bwd)


def quantized_matmul(x, w, *, x_scale, w_scale=None,
                     impl: Optional[str] = None,
                     interpret: bool = False,
                     block_m: Optional[int] = None,
                     block_n: Optional[int] = None):
    """int8 quantized matmul ``x @ w`` with a dequantize-fused epilogue.

    ``x``: ``[..., K]`` activations (bf16/fp32); ``w``: ``[K, N]``
    weights; ``x_scale``: the FROZEN per-tensor activation scale
    (``amax / 127`` from :mod:`apex_tpu.quant.calibrate` — do not pass a
    freshly computed ``abs(x).max()`` from the step function, that is
    recalibration-per-step and jaxlint J014 territory); ``w_scale``:
    per-channel ``[N]`` weight scales, computed from ``w`` when omitted.
    Returns ``x.dtype``, shaped ``[..., N]``.

    ``impl``: ``None`` picks pallas-vs-jnp by size (pallas only on TPU);
    ``"pallas"``/``"jnp"`` force a path.  ``interpret=True`` runs the
    Pallas kernel in interpreter mode (CPU tier-parity tests);
    ``impl="jnp"`` wins over it — that combination is the explicit
    "reference on this exact call" A/B probe.

    ``block_m``/``block_n``: explicit kernel tile overrides; left
    ``None`` the per-device config cache (:mod:`apex_tpu.tune`) is
    consulted with the hard-coded 256x256 defaults as the fallback
    (a tuned tile that fails the VMEM fit gate is ignored).

    Differentiable in ``x`` and ``w`` (straight-through, bf16 backward);
    the scales receive zero cotangents.
    """
    k = x.shape[-1]
    if w.ndim != 2 or w.shape[0] != k:
        raise ValueError(f"w must be [K={k}, N], got {w.shape}")
    if w_scale is None:
        w_scale = channel_scale(w)
    x_scale = jnp.reshape(jnp.asarray(x_scale, jnp.float32), ())
    w_scale = jnp.reshape(jnp.asarray(w_scale, jnp.float32), (w.shape[1],))
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    # _dispatch_pallas also validates impl; interpret forces the kernel
    # (interpreter mode) only when impl doesn't explicitly ask for the
    # jnp reference — impl="jnp" + interpret=True is the A/B probe
    # "reference on this exact call" and must stay honored.
    isz = jnp.dtype(x2d.dtype).itemsize
    use_pallas = _dispatch_pallas(x2d.shape[0], k, w.shape[1], impl, isz)
    if interpret and impl != "jnp":
        use_pallas = True
    if use_pallas and block_m is None and block_n is None:
        cfg = _tuned_config("quantized_matmul", TUNE_VERSION,
                            tune_bucket(x2d.shape[0], k, w.shape[1], isz),
                            params=("block_m", "block_n"))
        if cfg:
            tbm = _pick_block(x2d.shape[0], cfg.get("block_m", _BLOCK_M), 8)
            tbn = _pick_block(w.shape[1], cfg.get("block_n", _BLOCK_N), 128)
            if _kernel_fits(tbm, tbn, k, isz):
                block_m = cfg.get("block_m")
                block_n = cfg.get("block_n")
    out = _qmm(x2d, w, x_scale, w_scale, use_pallas, bool(interpret),
               block_m, block_n)
    return out.reshape(*lead, w.shape[1])
