"""``contrib.groupbn.BatchNorm2d_NHWC``, the norm every ResNet site of
the imagenet example builds, against ``flax.linen.BatchNorm`` followed by
the explicit add and ReLU (ISSUE 29): output, the four gradients and the
running statistics at the module's level, and ``bn_group`` 1 against 2 on
a mesh.  The op under the module (``bn_relu_residual``) is held against
autodiff in ``tests/test_fused_bn_act.py``; here the statistics, their
momentum, the parameter names and the eval path are in the comparison.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC

C = 8
MOMENTUM = 0.1          # the reference's: the weight of the new statistic


def _data(dtype, n=4, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, 6, 6, C) * 1.5 + 0.3, dtype)
    z = jnp.asarray(rng.randn(n, 6, 6, C), dtype)
    params = {"scale": jnp.asarray(rng.randn(C) * 0.5 + 1.0, jnp.float32),
              "bias": jnp.asarray(rng.randn(C) * 0.2, jnp.float32)}
    stats = {"mean": jnp.asarray(rng.randn(C) * 0.1, jnp.float32),
             "var": jnp.asarray(np.abs(rng.randn(C)) + 0.5, jnp.float32)}
    return x, z, params, stats


def _loss(y):
    return jnp.sum(jnp.sin(y.astype(jnp.float32)))


def _ours(fuse_relu, train, **kw):
    model = BatchNorm2d_NHWC(fuse_relu=fuse_relu, momentum=MOMENTUM,
                             use_running_average=not train, **kw)

    def fn(params, stats, x, z):
        y, updated = model.apply(
            {"params": {"bn": params},
             "batch_stats": {"bn": {"running_mean": stats["mean"],
                                    "running_var": stats["var"]}}},
            x, z, mutable=["batch_stats"])
        new = updated["batch_stats"]["bn"]
        return _loss(y), (y, {"mean": new["running_mean"],
                              "var": new["running_var"]})
    return fn


def _flax(fuse_relu, train, dtype):
    model = nn.BatchNorm(use_running_average=not train,
                         momentum=1.0 - MOMENTUM, epsilon=1e-5, dtype=dtype,
                         param_dtype=jnp.float32)

    def fn(params, stats, x, z):
        y, updated = model.apply({"params": params, "batch_stats": stats},
                                 x, mutable=["batch_stats"])
        if z is not None:
            y = z + y
        if fuse_relu:
            y = nn.relu(y)
        return _loss(y), (y, updated.get("batch_stats", stats))
    return fn


@pytest.mark.parametrize("train", [True, False], ids=["train", "eval"])
@pytest.mark.parametrize("residual", [True, False], ids=["z", "no_z"])
@pytest.mark.parametrize("fuse_relu", [True, False], ids=["relu", "linear"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_module_matches_flax_batchnorm(dtype, fuse_relu, residual, train):
    x, z, params, stats = _data(dtype)
    if not residual:
        z = None
    argnums = (0, 2, 3) if residual else (0, 2)
    (_, (y, new)), grads = jax.value_and_grad(
        _ours(fuse_relu, train), argnums=argnums, has_aux=True)(
        params, stats, x, z)
    (_, (y_ref, new_ref)), grads_ref = jax.value_and_grad(
        _flax(fuse_relu, train, dtype), argnums=argnums, has_aux=True)(
        params, stats, x, z)

    bf16 = dtype == jnp.bfloat16
    tol = 4e-2 if bf16 else 2e-5
    assert y.dtype == y_ref.dtype == dtype
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(grads_ref)):
        assert got.dtype == want.dtype and got.shape == want.shape
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        # bf16: flax adds the residual in bf16, the module in float32
        # before its one rounding; the per-channel sums average it out
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=tol,
                                   atol=tol * max(scale, 1.0))

    if not train:               # eval reads the statistics, writes none
        for name in stats:
            np.testing.assert_array_equal(np.asarray(new[name]),
                                          np.asarray(stats[name]))
        return
    np.testing.assert_allclose(np.asarray(new["mean"]),
                               np.asarray(new_ref["mean"]),
                               rtol=1e-5, atol=1e-6)
    # the running variance is the reference's (torch's) unbiased
    # estimate, flax's is the biased one
    count = x.size // C
    batch_var = (np.asarray(new_ref["var"])
                 - (1 - MOMENTUM) * np.asarray(stats["var"])) / MOMENTUM
    want_var = ((1 - MOMENTUM) * np.asarray(stats["var"])
                + MOMENTUM * batch_var * count / (count - 1))
    np.testing.assert_allclose(np.asarray(new["var"]), want_var,
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tail", ["bn", "bn_add_relu"])
@pytest.mark.parametrize("bn_group", [1, 2])
def test_bn_group_on_a_mesh_against_one_device(bn_group, tail):
    """Two replicas, half the batch each.  ``bn_group=2``: statistics over
    the whole batch, so everything equals the one-device module on the
    whole batch.  ``bn_group=1``: each half normalised on its own, the
    parameters' gradients summed over the halves, the running statistics
    the mean of the two halves' (what ``make_train_step``'s ``pmean``
    of the model state leaves)."""
    ndev = 2
    fuse = tail == "bn_add_relu"
    x, z, params, stats = _data(jnp.float32, n=2 * ndev, seed=3)
    if not fuse:
        z = None
    mesh = Mesh(np.array(jax.devices("cpu")[:ndev]), ("data",))
    argnums = (0, 2, 3) if fuse else (0, 2)

    def on_mesh(params, stats, x, z):
        def shard(params, stats, x, z):
            (_, (y, new)), grads = jax.value_and_grad(
                _ours(fuse, True, bn_group=bn_group, axis_name="data",
                      world_size=ndev),
                argnums=argnums, has_aux=True)(params, stats, x, z)
            return y, jax.lax.pmean(new, "data"), grads
        data = P("data")
        return shard_map(
            shard, mesh=mesh,
            in_specs=(P(), P(), data, data if fuse else None),
            out_specs=(data, P(), (P(), data, data) if fuse
                       else (P(), data)))(params, stats, x, z)

    def one_device(x, z):
        (_, (y, new)), grads = jax.value_and_grad(
            _ours(fuse, True), argnums=argnums, has_aux=True)(
            params, stats, x, z)
        return y, new, grads

    y, new, grads = jax.jit(on_mesh)(params, stats, x, z)
    if bn_group == ndev:
        y_ref, new_ref, grads_ref = one_device(x, z)
    else:
        halves = [one_device(x[i:i + 2], None if z is None else z[i:i + 2])
                  for i in (0, 2)]
        y_ref = jnp.concatenate([h[0] for h in halves])
        new_ref = jax.tree_util.tree_map(lambda a, b: (a + b) / 2,
                                         halves[0][1], halves[1][1])
        g0, g1 = halves[0][2], halves[1][2]
        grads_ref = (jax.tree_util.tree_map(jnp.add, g0[0], g1[0]),) \
            + tuple(jnp.concatenate([a, b]) for a, b in zip(g0[1:], g1[1:]))
        # and the halves do differ from the whole batch
        assert not np.allclose(np.asarray(y), np.asarray(one_device(x, z)[0]),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    for name in stats:
        np.testing.assert_allclose(np.asarray(new[name]),
                                   np.asarray(new_ref[name]),
                                   rtol=1e-5, atol=1e-6)
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
