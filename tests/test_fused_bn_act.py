"""BN epilogue tests (ISSUE 7, ISSUE 26, ISSUE 29): ``bn_relu_residual``'s
forward and hand-written backward against autodiff of a plain jnp
composition, its NHWC shape discipline (no activation-sized reshape or
pad), custom-VJP exactness through full-BN autodiff, the SyncBatchNorm
tail routing, and the ResNet norm-factory hook's fused-vs-explicit block
equivalence.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization.fused_bn_act import (bn_act_epilogue_ref,
                                                 bn_relu_residual)


def activation_sized(jaxpr, names, size):
    """Equations of ``jaxpr`` (sub-jaxprs included) whose primitive is in
    ``names`` and that read an operand of at least ``size`` elements."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names and any(
                getattr(v.aval, "size", 0) >= size for v in eqn.invars):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += activation_sized(sub, names, size)
    return found


def _operands(c=8, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(2, 5, 5, c), dtype)
    z = jnp.asarray(rng.randn(2, 5, 5, c), dtype)
    mean = jnp.asarray(rng.randn(c), jnp.float32)
    invstd = jnp.asarray(np.abs(rng.randn(c)) + 0.3, jnp.float32)
    w = jnp.asarray(rng.randn(c), jnp.float32)
    b = jnp.asarray(rng.randn(c), jnp.float32)
    return x, z, mean, invstd, w, b


def _plain_epilogue(x, mean, invstd, scale, bias, z, relu):
    """The epilogue's definition, written out here so that nothing of
    ``fused_bn_act`` stands on both sides of a comparison."""
    out = (x.astype(jnp.float32) - mean) * invstd
    if scale is not None:
        out = out * scale + bias
    if z is not None:
        out = out + z.astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("with_z", [True, False])
@pytest.mark.parametrize("affine", [True, False])
def test_forward_and_all_gradients_match_autodiff(dtype, relu, with_z,
                                                  affine):
    """The forward and the hand-written backward (``_bwd_ref``: the mask
    in the gradient's own dtype, two reductions for four per-channel
    cotangents) against autodiff of the plain composition, in every
    combination both ResNet cells run and the ones they do not."""
    x, z, mean, invstd, w, b = _operands(dtype=dtype, seed=1)
    args = {"x": x, "mean": mean, "invstd": invstd}
    if affine:
        args.update(scale=w, bias=b)
    if with_z:
        args["z"] = z

    def loss(fn, a):
        out = fn(a["x"], a["mean"], a["invstd"], a.get("scale"),
                 a.get("bias"), a.get("z"), relu)
        assert out.dtype == dtype
        # a cotangent that differs element by element
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    (_, got), g_got = jax.value_and_grad(
        functools.partial(loss, bn_relu_residual), has_aux=True)(args)
    (_, want), g_want = jax.value_and_grad(
        functools.partial(loss, _plain_epilogue), has_aux=True)(args)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert set(g_got) == set(args)
    for name in args:
        assert g_got[name].dtype == g_want[name].dtype == args[name].dtype
        assert g_got[name].shape == args[name].shape
        # x and z gradients are rounded to bf16 last; the per-channel
        # sums are float32 on both sides and differ by summation order
        tol = 1e-2 if g_got[name].dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(g_got[name], np.float32),
                                   np.asarray(g_want[name], np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_custom_vjp_exact_through_full_bn():
    """mean/invstd are differentiable inputs whose cotangents flow back
    into the XLA-side statistics — full-BN autodiff through the fused
    epilogue must equal plain-jnp composition autodiff."""
    x, z, _, _, w, b = _operands(seed=2)

    def full(use, xx, ww, bb, zz):
        xf = xx.astype(jnp.float32)
        m = xf.mean((0, 1, 2))
        inv = jax.lax.rsqrt(xf.var((0, 1, 2)) + 1e-5)
        if use:
            y = bn_relu_residual(xx, m, inv, ww, bb, z=zz, relu=True)
        else:
            y = jax.nn.relu((xf - m) * inv * ww + bb
                            + zz.astype(jnp.float32)).astype(xx.dtype)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    g_f = jax.grad(functools.partial(full, True),
                   argnums=(0, 1, 2, 3))(x, w, b, z)
    g_r = jax.grad(functools.partial(full, False),
                   argnums=(0, 1, 2, 3))(x, w, b, z)
    for a, r in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


def test_sync_batchnorm_tail_routes_through_epilogue():
    """SyncBatchNorm(channel_last=True) output is the epilogue applied
    to its own computed moments — op-identical (bitwise on CPU jnp)."""
    from apex_tpu.parallel import SyncBatchNorm

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(4, 6, 6, 5), jnp.float32)
    z = jnp.asarray(rng.randn(4, 6, 6, 5), jnp.float32)
    model = SyncBatchNorm(num_features=5, fuse_relu=True)
    variables = model.init(jax.random.PRNGKey(0), x, z)
    y, _ = model.apply(variables, x, z, mutable=["batch_stats"])
    xf = np.asarray(x).reshape(-1, 5)
    mean, var = xf.mean(0), xf.var(0)
    invstd = 1.0 / np.sqrt(var + 1e-5)
    want = bn_act_epilogue_ref(x, jnp.asarray(mean), jnp.asarray(invstd),
                               jnp.ones((5,)), jnp.zeros((5,)), z=z,
                               relu=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("with_z", [True, False])
def test_xla_side_issues_no_activation_sized_reshape_or_pad(with_z):
    """W = 28 is no multiple of the bf16 sublane tile, so on the TPU an
    ``[N,H,W,C] -> [rows,C]`` reshape is a physical copy: the epilogue,
    forward and backward, must stay on the NHWC array."""
    x = jnp.ones((2, 28, 28, 16), jnp.bfloat16)
    vec = jnp.ones((16,), jnp.float32)

    def loss(x, mean, invstd, scale, bias, z):
        out = bn_relu_residual(x, mean, invstd, scale, bias,
                               z=z if with_z else None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    closed = jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4, 5)))(x, vec, vec, vec, vec, x)
    assert "pallas_call" not in str(closed)
    assert not activation_sized(closed.jaxpr, ("reshape", "pad"), x.size)
    # the check can see one
    flat = jax.make_jaxpr(lambda x: bn_relu_residual(
        x.reshape(-1, 16), vec, vec, vec, vec))(x)
    assert activation_sized(flat.jaxpr, ("reshape",), x.size)


@pytest.mark.parametrize("with_z", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nhwc_xla_side_matches_reference(with_z, dtype):
    """Value and the six cotangents of the NHWC jnp epilogue (custom
    VJP, two reductions) against plain autodiff of
    ``bn_act_epilogue_ref``."""
    x, z, mean, invstd, w, b = _operands(c=16, dtype=dtype, seed=6)
    zz = z if with_z else None
    nargs = 6 if with_z else 5

    def loss(fn, xx, mm, ii, ww, bb, z_=None):
        out = fn(xx, mm, ii, ww, bb, z=z_, relu=True)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    args = (x, mean, invstd, w, b) + ((zz,) if with_z else ())
    sides = {"xla": bn_relu_residual, "ref": bn_act_epilogue_ref}
    got = {name: jax.value_and_grad(functools.partial(loss, fn),
                                    argnums=tuple(range(nargs)))(*args)
           for name, fn in sides.items()}
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(float(got["xla"][0]), float(got["ref"][0]),
                               rtol=tol)
    for a, r in zip(got["xla"][1], got["ref"][1]):
        assert a.shape == r.shape and a.dtype == r.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=tol, rtol=tol)


def test_resnet_norm_factory_fused_matches_explicit():
    """The block rewiring is routing, not math: a SyncBatchNorm ResNet
    (chains through the norm's epilogue) must match the plain
    ``nn.BatchNorm`` one (explicit relu/add statements) on the SAME
    parameters — loss and gradients."""
    from apex_tpu.models import ResNet18
    from apex_tpu.parallel import adopt_batchnorm_stats

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
    m_fused = ResNet18(num_classes=10, num_filters=8, sync_bn=True)
    m_plain = ResNet18(num_classes=10, num_filters=8)
    variables = m_plain.init(jax.random.PRNGKey(0), x, train=True)
    stats_plain = variables["batch_stats"]
    stats_fused = adopt_batchnorm_stats(stats_plain)
    # identical param trees: the hook changes no module names
    v2 = jax.eval_shape(lambda: m_fused.init(jax.random.PRNGKey(0), x,
                                             train=True))
    assert (jax.tree_util.tree_structure(variables["params"])
            == jax.tree_util.tree_structure(v2["params"]))
    assert (jax.tree_util.tree_structure(stats_fused)
            == jax.tree_util.tree_structure(v2["batch_stats"]))

    def fwd(model, stats, p):
        y, _ = model.apply({"params": p, "batch_stats": stats},
                           x, train=True, mutable=["batch_stats"])
        return jnp.sum(y ** 2)

    y_f, g_f = jax.jit(jax.value_and_grad(
        functools.partial(fwd, m_fused, stats_fused)))(variables["params"])
    y_p, g_p = jax.jit(jax.value_and_grad(
        functools.partial(fwd, m_plain, stats_plain)))(variables["params"])
    np.testing.assert_allclose(float(y_f), float(y_p), rtol=1e-5)
    for a, r in zip(jax.tree_util.tree_leaves(g_f),
                    jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-3)


def test_resnet_groupbn_norm_cls_end_to_end():
    """The imagenet example's wiring: ResNet over
    contrib.groupbn.BatchNorm2d_NHWC trains a step and keeps its
    keep-bn-fp32-friendly param paths (bn*/bn/scale)."""
    from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
    from apex_tpu.models import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.bfloat16,
                     norm_cls=functools.partial(BatchNorm2d_NHWC))
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    assert "bn" in variables["params"]["bn_init"]          # nested module
    y, _ = model.apply(variables, x, train=True, mutable=["batch_stats"])
    assert y.shape == (2, 10) and np.isfinite(np.asarray(y)).all()
