"""contrib tests — label-smoothing xentropy vs pure-jnp references (reference
contrib/test/test_label_smoothing.py:10-28 pattern: fused vs two torch
references, fwd+bwd) and GroupBN NHWC semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from apex_tpu.contrib.xentropy import (SoftmaxCrossEntropyLoss,
                                       softmax_cross_entropy_loss)
from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC


def _raw_reference(x, target, padding_idx, smoothing):
    """reference label_smoothing_raw (test_label_smoothing.py:10-18)."""
    logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    smooth = -jnp.mean(logp, axis=-1)
    loss = (1.0 - smoothing) * nll + smoothing * smooth
    return jnp.where(target == padding_idx, 0.0, loss)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xentropy_forward_matches_reference(smoothing, dtype):
    rng = np.random.RandomState(0)
    n, h = 64, 512
    x = jnp.asarray(rng.randn(n, h), dtype)
    labels = jnp.asarray(rng.randint(0, h, n))
    labels = labels.at[::6].set(0)   # padding hits (reference: 1/6 padded)
    got = softmax_cross_entropy_loss(x, labels, smoothing, padding_idx=0)
    want = _raw_reference(x, labels, 0, smoothing)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)
    # padded rows exactly zero
    np.testing.assert_array_equal(np.asarray(got[::6]), 0.0)


def test_xentropy_backward_matches_autodiff_reference():
    rng = np.random.RandomState(1)
    n, h = 32, 128
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    labels = jnp.asarray(rng.randint(1, h, n)).at[::5].set(0)

    def fused(xx):
        return jnp.sum(softmax_cross_entropy_loss(xx, labels, 0.1, 0))

    def ref(xx):
        return jnp.sum(_raw_reference(xx, labels, 0, 0.1))

    g_fused = jax.grad(fused)(x)
    g_ref = jax.grad(ref)(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               atol=1e-5)
    # padded rows give zero gradient
    np.testing.assert_array_equal(np.asarray(g_fused[::5]), 0.0)


def test_xentropy_apply_interface_and_jit():
    x = jnp.ones((8, 16))
    labels = jnp.asarray(np.arange(8) % 16)
    out = jax.jit(lambda a, b: SoftmaxCrossEntropyLoss.apply(a, b, 0.1, -1))(
        x, labels)
    assert out.shape == (8,)
    np.testing.assert_allclose(np.asarray(out), np.log(16), atol=1e-5)


def test_groupbn_local_when_group_1():
    model = BatchNorm2d_NHWC(num_features=4)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 5, 4), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    y, _ = model.apply(variables, x, mutable=["batch_stats"])
    yf = np.asarray(y).reshape(-1, 4)
    np.testing.assert_allclose(yf.mean(0), 0.0, atol=1e-5)
    np.testing.assert_allclose(yf.std(0), 1.0, atol=1e-2)


def test_groupbn_fuse_relu_and_z_add():
    model = BatchNorm2d_NHWC(num_features=4, fuse_relu=True)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 5, 4), jnp.float32)
    z = jnp.asarray(np.random.RandomState(1).randn(2, 5, 5, 4), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, z)
    y, _ = model.apply(variables, x, z, mutable=["batch_stats"])
    assert float(jnp.min(y)) >= 0.0   # relu applied after the z add


def test_groupbn_validation_errors():
    x = jnp.ones((2, 4, 4, 4))
    with pytest.raises(ValueError, match="axis_name"):
        BatchNorm2d_NHWC(num_features=4, bn_group=4, world_size=8).init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="divisible"):
        BatchNorm2d_NHWC(num_features=4, bn_group=4, world_size=6,
                         axis_name="data").init(jax.random.PRNGKey(0), x)


# -- tier parity (ISSUE 7 satellite): the REAL pallas kernels, interpret
# mode on CPU, vs the _fwd_ref/_bwd_ref oracles -------------------------------

from apex_tpu.contrib.xentropy import (_bwd_ref, _fwd_grad_pallas,
                                       _fwd_pallas, _fwd_ref)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_xentropy_pallas_interpret_forward_parity(smoothing):
    """Both forward kernels: the primal one and the one that runs under
    differentiation give the reference's losses."""
    rng = np.random.RandomState(2)
    n, h = 48, 256
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    labels = jnp.asarray(rng.randint(0, h, n), jnp.int32)
    loss_k, mlse_k = _fwd_pallas(x, labels, smoothing, interpret=True)
    loss_g, _ = _fwd_grad_pallas(x, labels, smoothing, interpret=True)
    loss_r, mlse_r = _fwd_ref(x, labels, smoothing)
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(loss_g), np.asarray(loss_r),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(mlse_k), np.asarray(mlse_r),
                               atol=1e-5)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_xentropy_pallas_interpret_backward_parity(smoothing):
    """Kernel-vs-reference grad parity including the padding corner.  The
    forward kernel under differentiation leaves ``r``, the gradient short
    of the incoming factor (``_bwd_ref`` with ``g = 1``); the custom VJP
    masks padded rows' incoming grads and multiplies, so ``g * r`` is held
    against ``_bwd_ref`` with exactly that masked input."""
    rng = np.random.RandomState(3)
    n, h = 40, 128
    padding_idx = 0
    x = jnp.asarray(rng.randn(n, h), jnp.float32)
    labels = jnp.asarray(rng.randint(1, h, n), jnp.int32)
    labels = labels.at[::5].set(padding_idx)         # padded rows
    _, mlse = _fwd_ref(x, labels, smoothing)
    _, r_k = _fwd_grad_pallas(x, labels, smoothing, interpret=True)
    r_r = _bwd_ref(jnp.ones(n, jnp.float32), x, mlse, labels, smoothing)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r), atol=1e-5)
    g = jnp.asarray(rng.rand(n), jnp.float32)
    g = jnp.where(labels == padding_idx, 0.0, g)     # the vjp's mask
    dx_k = g[:, None] * r_k                          # the vjp's product
    dx_r = _bwd_ref(g, x, mlse, labels, smoothing)
    np.testing.assert_allclose(np.asarray(dx_k), np.asarray(dx_r),
                               atol=1e-5)
    # padded rows: exactly zero through the kernel's residual too
    np.testing.assert_array_equal(np.asarray(dx_k[::5]), 0.0)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xentropy_grad_equals_grad_of_the_reference_loss(smoothing, dtype):
    """``jax.grad`` of the public op against ``jax.grad`` of a loss written
    from ``_fwd_ref``, padded rows and uneven incoming factors included."""
    rng = np.random.RandomState(6)
    n, h = 24, 96
    padding_idx = 0
    x = jnp.asarray(rng.randn(n, h), dtype)
    labels = jnp.asarray(rng.randint(1, h, n), jnp.int32)
    labels = labels.at[::4].set(padding_idx)
    weights = jnp.asarray(rng.rand(n), jnp.float32)

    def fused(xx):
        return jnp.sum(weights * softmax_cross_entropy_loss(
            xx, labels, smoothing, padding_idx))

    def ref(xx):
        losses, _ = _fwd_ref(xx, labels, smoothing)
        return jnp.sum(weights * jnp.where(labels == padding_idx, 0.0,
                                           losses))

    got, want = jax.grad(fused)(x), jax.grad(ref)(x)
    assert got.dtype == want.dtype == dtype
    # bfloat16: ``r`` is rounded before the multiplication and the product
    # once more, two roundings of values below 1 where autodiff makes one
    tol = 1e-5 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    np.testing.assert_array_equal(np.asarray(got[::4], np.float32), 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xentropy_residual_is_r_and_labels_alone(dtype):
    """Under differentiation neither the logits nor ``mlse`` is kept: the
    residuals are one ``[N, H]`` array in the logits' dtype and the labels."""
    x = jnp.zeros((16, 64), dtype)
    labels = jnp.arange(16, dtype=jnp.int32)
    _, vjp = jax.vjp(lambda xx: softmax_cross_entropy_loss(xx, labels, 0.1),
                     x)
    kept = sorted((leaf.shape, str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(vjp))
    assert kept == sorted([((16, 64), str(jnp.dtype(dtype))),
                           ((16,), "int32")])


def test_groupbn_z_add_relu_matches_oracle():
    """Quantitative oracle for the fused bn(+z)+relu epilogue through
    the groupbn module (not just sign checks): batch moments computed
    independently, the whole chain in fp64-free numpy."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(3, 6, 6, 5), jnp.float32)
    z = jnp.asarray(rng.randn(3, 6, 6, 5), jnp.float32)
    model = BatchNorm2d_NHWC(num_features=5, fuse_relu=True)
    variables = model.init(jax.random.PRNGKey(0), x, z)
    y, _ = model.apply(variables, x, z, mutable=["batch_stats"])
    xf = np.asarray(x).reshape(-1, 5)
    mean, var = xf.mean(0), xf.var(0)
    want = np.maximum(
        (np.asarray(x) - mean) / np.sqrt(var + 1e-5) + np.asarray(z), 0.0)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)


def test_groupbn_bn_group_sync_on_mesh():
    """bn_group=4 on an 8-replica mesh: stats shared within each half."""
    mesh = Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
    model = BatchNorm2d_NHWC(num_features=3, bn_group=4, axis_name="data",
                             world_size=8)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8 * 2, 4, 4, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x[:2])

    def fwd(xs):
        y, _ = model.apply(variables, xs, mutable=["batch_stats"])
        return y

    y = jax.jit(shard_map(fwd, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))(x)
    # Oracle: normalize each half-batch (ranks 0-3 see x[:8], ranks 4-7 x[8:])
    for lo, hi in ((0, 8), (8, 16)):
        seg = np.asarray(x[lo:hi]).reshape(-1, 3)
        mean, var = seg.mean(0), seg.var(0)
        want = (np.asarray(x[lo:hi]) - mean) / np.sqrt(var + 1e-5)
        np.testing.assert_allclose(np.asarray(y[lo:hi]), want, atol=1e-4)
