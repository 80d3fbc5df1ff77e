"""SyncBatchNorm — cross-replica batch normalization over mesh axes.

TPU-native re-design of reference ``apex/parallel/sync_batchnorm.py`` (python
fallback) and ``optimized_sync_batchnorm*.py`` + ``csrc/welford.cu`` (CUDA
path).  One implementation replaces both:

* Local statistics are computed per replica, then combined across the mesh
  axis with a **count-weighted Welford-style parallel combine**
  (``welford_parallel``: reference ``csrc/welford.cu:558-586`` Chan et al.
  algorithm) expressed with ``lax.psum`` of (sum, sum_sq, count) — this
  handles unequal per-replica batches, which the reference python fallback's
  plain mean-of-means does not.
* The backward pass needs no hand-written kernel: the transpose of ``psum``
  is ``psum``, so autodiff derives exactly the reference's
  ``mean_dy``/``mean_dy_xmu`` allreduce structure
  (``sync_batchnorm_kernel.py:54-70``) from the forward.
* ``channel_last`` is the native layout on TPU (NHWC); ``fuse_relu``
  reproduces the optimized module's fused BN(+z)+ReLU epilogue
  (``optimized_sync_batchnorm.py:9-89``) — XLA fuses it into the normalize.
* BN process groups (``group_size`` sub-worlds) map to ``axis_index_groups``
  (reference ``create_syncbn_process_group``, ``parallel/__init__.py:55-96``).

Running stats follow the torch convention: ``running = (1-momentum)*running +
momentum*batch_stat`` with *unbiased* batch variance (reference
``sync_batchnorm.py:95-131``), stored in the flax ``batch_stats`` collection.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
import flax.linen as nn


def welford_parallel(mean, var, count):
    """Combine per-replica (mean, biased var, count) into global stats.

    Functional form of ``syncbn.welford_parallel`` (``welford.cu:558-586``):
    given stacked per-group stats along axis 0, returns combined (mean, var).
    Used directly by tests as the oracle; inside the module the same math is
    expressed with psums for efficiency.
    """
    count = jnp.asarray(count, jnp.float32)
    total = jnp.sum(count, axis=0)
    mean_all = jnp.sum(mean * count, axis=0) / total
    # E[x^2] recombination: var_g + mean_g^2 weighted.
    ex2 = jnp.sum((var + mean ** 2) * count, axis=0) / total
    return mean_all, ex2 - mean_all ** 2


def _global_moments(x, reduce_axes, axis_name, axis_index_groups):
    """Cross-replica mean/var over ``reduce_axes`` of x (fp32 accumulation).

    Equivalent of welford_mean_var + all_gather + welford_parallel
    (``optimized_sync_batchnorm_kernel.py:22-55``), expressed as psum of
    (sum, sum_sq, count) — one fused all-reduce on the wire.
    """
    xf = x.astype(jnp.float32)
    local_sum = jnp.sum(xf, axis=reduce_axes)
    local_sqr = jnp.sum(jnp.square(xf), axis=reduce_axes)
    local_count = jnp.float32(1.0)
    for a in reduce_axes:
        local_count = local_count * x.shape[a]
    count = jnp.broadcast_to(local_count, local_sum.shape)
    if axis_name is not None:
        stacked = jnp.concatenate([local_sum, local_sqr, count])
        from .distributed import group_psum
        stacked = group_psum(stacked, axis_name, axis_index_groups)
        n = local_sum.shape[0]
        total_sum, total_sqr, total_count = (stacked[:n], stacked[n:2 * n],
                                             stacked[2 * n:])
    else:
        total_sum, total_sqr, total_count = local_sum, local_sqr, count
    mean = total_sum / total_count
    var = total_sqr / total_count - jnp.square(mean)
    return mean, var, total_count


class SyncBatchNorm(nn.Module):
    """Flax module with ``_BatchNorm`` semantics synced across a mesh axis.

    Args mirror the reference module (``sync_batchnorm.py:9-134`` +
    ``optimized_sync_batchnorm.py``): ``momentum`` is the *torch* momentum
    (weight of the new batch stat), ``process_group`` is an
    ``axis_index_groups`` list, ``channel_last`` chooses NHWC (the TPU-native
    layout, default True), ``fuse_relu`` fuses the optional ``z``-add and
    ReLU epilogue.
    """
    num_features: Optional[int] = None
    eps: float = 1e-5
    momentum: float = 0.1
    affine: bool = True
    track_running_stats: bool = True
    axis_name: Optional[str] = None
    process_group: Optional[Sequence[Sequence[int]]] = None
    channel_last: bool = True
    fuse_relu: bool = False
    use_running_average: Optional[bool] = None
    scale_init: Any = nn.initializers.ones
    bias_init: Any = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, z=None, use_running_average: Optional[bool] = None):
        use_ra = use_running_average
        if use_ra is None:
            use_ra = self.use_running_average
        if use_ra is None:
            use_ra = False

        if self.channel_last:
            channel_axis = x.ndim - 1
        else:
            channel_axis = 1
        reduce_axes = tuple(a for a in range(x.ndim) if a != channel_axis)
        num_features = self.num_features or x.shape[channel_axis]
        stat_shape = tuple(num_features if a == channel_axis else 1
                           for a in range(x.ndim))

        ra_mean = self.variable("batch_stats", "running_mean",
                                lambda: jnp.zeros((num_features,), jnp.float32))
        ra_var = self.variable("batch_stats", "running_var",
                               lambda: jnp.ones((num_features,), jnp.float32))

        if use_ra:
            # Eval: F.batch_norm fallback on running stats (reference
            # sync_batchnorm.py:85-88).
            mean, var = ra_mean.value, ra_var.value
        else:
            # During module init there is no bound mesh axis; stats stay
            # local (same convention as flax BatchNorm).
            axis = None if self.is_initializing() else self.axis_name
            mean, var, total_count = _global_moments(
                x, reduce_axes, axis, self.process_group)
            if self.track_running_stats and not self.is_initializing():
                # Unbiased var for running stats (reference :95-131).
                unbiased = var * total_count / jnp.maximum(total_count - 1, 1)
                ra_mean.value = ((1 - self.momentum) * ra_mean.value
                                 + self.momentum * mean)
                ra_var.value = ((1 - self.momentum) * ra_var.value
                                + self.momentum * unbiased)

        invstd = lax.rsqrt(var + self.eps)
        weight = bias = None
        if self.affine:
            weight = self.param("scale", self.scale_init,
                                (num_features,), jnp.float32)
            bias = self.param("bias", self.bias_init,
                              (num_features,), jnp.float32)
        if self.channel_last:
            # The elementwise tail (normalize, affine, the optional
            # ``z`` residual add of the reference's batch_norm_add_relu,
            # the ReLU) is ``bn_relu_residual``: plain jnp on ``x`` as it
            # is, which XLA fuses into its neighbours, under a custom VJP
            # that saves ``x`` and ``z`` and hands the statistics their
            # cotangents exactly.
            from ..normalization.fused_bn_act import bn_relu_residual
            return bn_relu_residual(x, mean, invstd, weight, bias, z=z,
                                    relu=self.fuse_relu)
        out = (x.astype(jnp.float32)
               - mean.reshape(stat_shape)) * invstd.reshape(stat_shape)
        if self.affine:
            out = out * weight.reshape(stat_shape) + bias.reshape(stat_shape)
        if z is not None:
            # BN-add(-relu) fusion input (reference batch_norm_add_relu).
            out = out + z.astype(jnp.float32)
        if self.fuse_relu:
            out = jax.nn.relu(out)
        return out.astype(x.dtype)


def adopt_batchnorm_stats(batch_stats):
    """Rename plain flax ``BatchNorm`` running stats (``mean``/``var``)
    to :class:`SyncBatchNorm`'s reference names
    (``running_mean``/``running_var``), recursively, leaving everything
    else untouched.

    The standard init recipe uses plain ``BatchNorm`` (SyncBatchNorm's
    collectives need a bound mesh axis, absent at init) and swaps in the
    sync module for training.  Without the rename the first sync apply
    would CREATE its differently-named stats, growing the
    ``batch_stats`` pytree mid-training — a silent retrace on the
    jitted-per-step path and a hard error for scan-carried state
    (:class:`apex_tpu.runtime.StepPipeline` requires structure-stable
    carries).  Values are preserved (both modules init zeros/ones).
    """
    def _rename(d):
        if isinstance(d, dict):
            if set(d) == {"mean", "var"}:
                return {"running_mean": d["mean"],
                        "running_var": d["var"]}
            return {k: _rename(v) for k, v in d.items()}
        return d
    return _rename(batch_stats)
