"""Fused label-smoothing softmax-cross-entropy.

TPU-native re-design of reference ``apex/contrib/xentropy/softmax_xentropy.py``
+ ``apex/contrib/csrc/xentropy/xentropy_kernel.cu``:

* the primal forward returns per-example ``losses`` (and, inside,
  ``max_log_sum_exp``, one fp32 scalar per row, as the CUDA kernel's
  interface does) and writes no ``[N, H]`` array.
* under differentiation the loss reads its logits once: the forward kernel,
  which already holds the row and its log-sum-exp, also computes
  ``r = softmax - (1-s)·onehot - s/H`` and writes it over the logits
  (``input_output_aliases``: for float32 logits no second ``[N, H]`` array
  exists; XLA copies only where a caller reads the logits after the loss).
  The residuals are ``(r, labels)``; the backward is ``d logits = g * r``,
  one broadcast multiplication in ``jax.numpy`` that XLA fuses into the
  consumers (the head's two gradient products), with no kernel of its own;
  it does so only where no reshape stands between, which is why the models'
  heads multiply over flattened tokens (``models.granite_hybrid.head_logits``).
  For float32 logits ``g * r`` is the product the old backward kernel
  computed, bit for bit.  For logits narrower than float32 ``r`` is rounded
  to the logits' dtype before the multiplication and the product once more.
* positions where ``labels == padding_idx`` contribute zero loss and zero
  gradient (reference ``softmax_xentropy.py:9,23``).

Loss definition (reference test oracle ``test_label_smoothing.py:10-28``)::

    loss = (1-smoothing) * nll + smoothing * smooth_loss
    nll = logsumexp(x) - x[label];  smooth_loss = logsumexp(x) - mean(x)

On TPU a Pallas kernel processes a block of rows per grid step (row max /
sum-exp on the VPU, label extraction via iota-select); off TPU the same math
runs as jnp and stores the same residual, doubling as the oracle.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax

from ...pallas_compat import sds_with_vma as _sds
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...normalization.fused_layer_norm import _use_pallas
from ...tune.dispatch import kernel_config as _tuned_config
from ...tune.space import pow2_bucket as _pow2

__all__ = ["SoftmaxCrossEntropyLoss", "softmax_cross_entropy_loss"]

#: config-cache version of this kernel's blocking scheme (ISSUE 14; 2 since
#: the forward kernel under differentiation also writes ``[R, H]`` blocks).
TUNE_VERSION = 2


# -- reference math (jnp fallback + oracle) -----------------------------------

def _fwd_ref(logits, labels, smoothing):
    xf = logits.astype(jnp.float32)
    h = xf.shape[-1]
    m = jnp.max(xf, axis=-1)
    mlse = m + jnp.log(jnp.sum(jnp.exp(xf - m[:, None]), axis=-1))
    label_logit = jnp.take_along_axis(xf, labels[:, None], axis=-1)[:, 0]
    mean_logit = jnp.mean(xf, axis=-1)
    losses = mlse - (1.0 - smoothing) * label_logit - smoothing * mean_logit
    return losses, mlse


def _bwd_ref(g, logits, mlse, labels, smoothing):
    xf = logits.astype(jnp.float32)
    h = xf.shape[-1]
    soft = jnp.exp(xf - mlse[:, None])
    onehot = jax.nn.one_hot(labels, h, dtype=jnp.float32)
    dx = g[:, None] * (soft - (1.0 - smoothing) * onehot - smoothing / h)
    return dx.astype(logits.dtype)


# -- pallas kernels -----------------------------------------------------------

_ROW_BLOCK = 128
_VMEM_BUFFER_BUDGET = 2 * 1024 * 1024   # bytes per fp32 [R, H] working buffer


def _row_block(n, h, row_block=None):
    """Rows per grid step, sized so the fp32 [R, H] working buffers stay
    inside the TPU's ~16MB scoped-VMEM limit even for LM-head-sized
    vocabularies (e.g. H=50257).  The forward kernel under differentiation
    holds the logits block and the ``r`` block, both double-buffered by the
    pipeline, and up to ~4 live [R, H] intermediates (exp(x - max), iota /
    onehot, softmax, ``r`` before its store), hence the conservative
    per-buffer budget.  ``row_block`` overrides the 128-row
    cap (the autotuner's knob, ISSUE 14); the budget clamp below it
    keeps any tuned value VMEM-legal."""
    rows = min(row_block or _ROW_BLOCK, _VMEM_BUFFER_BUDGET // (4 * h))
    rows = max(8, (rows // 8) * 8)      # sublane multiple
    return min(rows, max(8, n))


def tune_bucket(n, h):
    """Config-cache shape bucket: vocab width exact (it sets the budget
    math), rows rounded to a power of two."""
    return f"r{_pow2(n)}_h{h}"


def _tuned_rows(n, h):
    """Dispatch-time consult (ISSUE 14): the tuned ``row_block`` for
    this shape bucket, or None (the hard-coded default)."""
    cfg = _tuned_config("xentropy", TUNE_VERSION, tune_bucket(n, h),
                        params=("row_block",))
    return cfg["row_block"] if cfg else None


def _pallas_fits(h):
    """Even the minimum 8-row block must fit the scoped-VMEM budget."""
    return 8 * h * 4 <= 2 * _VMEM_BUFFER_BUDGET


# Per-row vectors (labels, losses, mlse, incoming grads) travel as [R, 1]
# 2-D arrays: Mosaic requires lane-tiled ≥2-D layouts; 1-D s32 operands hit
# an XLA/Mosaic layout mismatch on real TPUs.

def _block_losses(x_ref, lab_ref, smoothing):
    """One row block's ``(xf, onehot mask, losses, mlse)``."""
    xf = x_ref[:].astype(jnp.float32)                   # [R, H]
    h = xf.shape[1]
    m = jnp.max(xf, axis=1, keepdims=True)
    mlse = m + jnp.log(jnp.sum(jnp.exp(xf - m), axis=1, keepdims=True))
    lab = lab_ref[:]                                    # [R, 1]
    col = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
    hit = col == lab
    picked = jnp.sum(jnp.where(hit, xf, 0.0), axis=1, keepdims=True)
    mean_logit = jnp.sum(xf, axis=1, keepdims=True) / h
    losses = mlse - (1.0 - smoothing) * picked - smoothing * mean_logit
    return xf, hit, losses, mlse


def _fwd_kernel(x_ref, lab_ref, loss_ref, mlse_ref, *, smoothing):
    _, _, loss_ref[:], mlse_ref[:] = _block_losses(x_ref, lab_ref, smoothing)


def _fwd_grad_kernel(x_ref, lab_ref, loss_ref, r_ref, *, smoothing):
    """``_fwd_kernel``'s losses and, from the same ``xf`` in VMEM, the
    gradient short of the incoming factor; ``r_ref`` is the logits' buffer."""
    xf, hit, loss_ref[:], mlse = _block_losses(x_ref, lab_ref, smoothing)
    soft = jnp.exp(xf - mlse)
    onehot = hit.astype(jnp.float32)
    r = soft - (1.0 - smoothing) * onehot - smoothing / xf.shape[1]
    r_ref[:] = r.astype(r_ref.dtype)


def _fwd_pallas(logits, labels, smoothing, interpret=False,
                row_block=None):
    n, h = logits.shape
    blk = _row_block(n, h, row_block)
    grid = (n + blk - 1) // blk
    loss, mlse = pl.pallas_call(
        functools.partial(_fwd_kernel, smoothing=smoothing),
        grid=(grid,),
        in_specs=[pl.BlockSpec((blk, h), lambda i: (i, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_shape=[_sds((n, 1), jnp.float32, logits),
                   _sds((n, 1), jnp.float32, logits)],
        interpret=interpret,   # CPU tier-parity tests run the REAL kernel
    )(logits, labels[:, None])
    return loss[:, 0], mlse[:, 0]


def _fwd_grad_pallas(logits, labels, smoothing, interpret=False,
                     row_block=None):
    """``(losses, r)``; ``r`` takes the logits' buffer."""
    n, h = logits.shape
    blk = _row_block(n, h, row_block)
    grid = (n + blk - 1) // blk
    loss, r = pl.pallas_call(
        functools.partial(_fwd_grad_kernel, smoothing=smoothing),
        grid=(grid,),
        in_specs=[pl.BlockSpec((blk, h), lambda i: (i, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((blk, h), lambda i: (i, 0))],
        out_shape=[_sds((n, 1), jnp.float32, logits),
                   _sds((n, h), logits.dtype, logits)],
        input_output_aliases={0: 1},
        interpret=interpret,
    )(logits, labels[:, None])
    return loss[:, 0], r


# -- public op with custom VJP ------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def softmax_cross_entropy_loss(logits, labels, smoothing=0.0, padding_idx=0,
                               half_to_float=False):
    """Per-example label-smoothing cross entropy, padding masked to zero.

    ``half_to_float`` kept for reference signature parity (bf16 losses are
    always computed and returned in fp32 here, like the CUDA kernel's
    fp32 accumulation).
    """
    losses, _ = _fwd_impl(logits, labels, smoothing)
    return jnp.where(labels == padding_idx, 0.0, losses)


def _fwd_impl(logits, labels, smoothing):
    labels = labels.astype(jnp.int32)
    if _use_pallas() and _pallas_fits(logits.shape[-1]):
        n, h = logits.shape
        return _fwd_pallas(logits, labels, smoothing,
                           row_block=_tuned_rows(n, h))
    return _fwd_ref(logits, labels, smoothing)


def _fwd_vjp(logits, labels, smoothing, padding_idx, half_to_float):
    labels = labels.astype(jnp.int32)
    if _use_pallas() and _pallas_fits(logits.shape[-1]):
        n, h = logits.shape
        losses, r = _fwd_grad_pallas(logits, labels, smoothing,
                                     row_block=_tuned_rows(n, h))
    else:
        losses, mlse = _fwd_ref(logits, labels, smoothing)
        r = _bwd_ref(jnp.ones_like(mlse), logits, mlse, labels, smoothing)
    losses = jnp.where(labels == padding_idx, 0.0, losses)
    return losses, (r, labels)


def _bwd_vjp(smoothing, padding_idx, half_to_float, res, g):
    r, labels = res
    g = jnp.where(labels == padding_idx, 0.0, g.astype(jnp.float32))
    dx = (g[:, None] * r.astype(jnp.float32)).astype(r.dtype)
    return dx, None


softmax_cross_entropy_loss.defvjp(_fwd_vjp, _bwd_vjp)


class SoftmaxCrossEntropyLoss:
    """Reference-compatible callable (``softmax_xentropy.py:4-28`` exposes
    ``SoftmaxCrossEntropyLoss.apply(...)``)."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0,
              half_to_float=False):
        return softmax_cross_entropy_loss(logits, labels, smoothing,
                                          padding_idx, half_to_float)

    def __call__(self, logits, labels, smoothing=0.0, padding_idx=0,
                 half_to_float=False):
        return self.apply(logits, labels, smoothing, padding_idx,
                          half_to_float)
