"""BN epilogue tests (ISSUE 7, ISSUE 26): bn_relu_residual kernel parity
(interpret mode vs the jnp reference), the NHWC XLA implementation's
parity with both, the automatic dispatch (XLA at every ResNet-50 site,
the kernel only when forced), custom-VJP exactness through full-BN
autodiff, the SyncBatchNorm tail routing, and the ResNet norm-factory
hook's fused-vs-explicit block equivalence.
"""

import functools
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import fused_bn_act
from apex_tpu.normalization.fused_bn_act import (_dispatch_pallas,
                                                 _kernel_fits,
                                                 bn_act_epilogue_ref,
                                                 bn_relu_residual)


def load_chip_smoke():
    """``chip_smoke.py`` of this checkout as a module (its import touches
    neither JAX nor the chip): the lists of ResNet-50 sites live there."""
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("with_z", [True, False])
@pytest.mark.parametrize("affine", [True, False])
def test_kernel_interpret_forward_parity(dtype, relu, with_z, affine):
    x, z, mean, invstd, w, b = _operands(dtype=dtype)
    zz = z if with_z else None
    ww, bb = (w, b) if affine else (None, None)
    got = bn_relu_residual(x, mean, invstd, ww, bb, z=zz, relu=relu,
                           interpret=True)
    want = bn_act_epilogue_ref(x, mean, invstd, ww, bb, z=zz, relu=relu)
    assert got.dtype == x.dtype
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_kernel_interpret_gradient_parity_all_inputs():
    x, z, mean, invstd, w, b = _operands(seed=1)

    def loss(interp, xx, mm, ii, ww, bb, zz):
        return jnp.sum(bn_relu_residual(xx, mm, ii, ww, bb, z=zz,
                                        relu=True, interpret=interp) ** 2)

    g_k = jax.grad(functools.partial(loss, True),
                   argnums=(0, 1, 2, 3, 4, 5))(x, mean, invstd, w, b, z)
    g_r = jax.grad(functools.partial(loss, False),
                   argnums=(0, 1, 2, 3, 4, 5))(x, mean, invstd, w, b, z)
    for a, r in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


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


def test_dispatch_gates(monkeypatch):
    """Off-TPU the dispatch always takes jnp, forced or not; on the TPU
    the kernel runs only when forced, and the width gate keeps blocks
    whose 8-row floor exceeds scoped VMEM off it even then."""
    assert not _dispatch_pallas(256, None, 4)            # no TPU backend
    assert not _dispatch_pallas(256, "pallas", 4)
    with pytest.raises(ValueError, match="impl"):
        _dispatch_pallas(8, "mosaic", 4)
    assert _kernel_fits(256, 4)
    assert not _kernel_fits(10 ** 6, 4)                  # 8-row floor OOM
    monkeypatch.setattr(fused_bn_act, "_use_pallas", lambda: True)
    assert _dispatch_pallas(256, "pallas", 4)
    assert not _dispatch_pallas(256, "jnp", 4)
    assert not _dispatch_pallas(10 ** 6, "pallas", 4)


CHIP_SMOKE = load_chip_smoke()


@pytest.mark.parametrize("rows,c,has_z", CHIP_SMOKE.RESNET50_BN)
def test_resnet50_site_takes_xla_unless_forced(rows, c, has_z, monkeypatch):
    """Every BN site of the ResNet-50 step at b256 (chip_smoke lists
    them by their rows at its own batch), bf16, as if on the TPU: the
    automatic choice is XLA, ``impl="pallas"`` is still the kernel
    (PERF.md section 6, PR 26)."""
    monkeypatch.setattr(fused_bn_act, "_use_pallas", lambda: True)
    assert not _dispatch_pallas(c, None, 2)
    assert _dispatch_pallas(c, "pallas", 2)
    hw = int(round((rows // CHIP_SMOKE.BATCH_PER_CHIP) ** 0.5))
    x = jax.ShapeDtypeStruct((256, hw, hw, c), jnp.bfloat16)
    vec = jax.ShapeDtypeStruct((c,), jnp.float32)

    def site(impl, x, mean, invstd, scale, bias):
        return bn_relu_residual(x, mean, invstd, scale, bias,
                                z=x if has_z else None, impl=impl)

    auto = str(jax.make_jaxpr(functools.partial(site, None))(
        x, vec, vec, vec, vec))
    forced = str(jax.make_jaxpr(functools.partial(site, "pallas"))(
        x, vec, vec, vec, vec))
    assert "pallas_call" not in auto and "pallas_call" in forced


@pytest.mark.parametrize("with_z", [True, False])
def test_xla_side_issues_no_activation_sized_reshape_or_pad(with_z,
                                                            monkeypatch):
    """W = 28 is no multiple of the bf16 sublane tile, so on the TPU an
    ``[N,H,W,C] -> [rows,C]`` reshape is a physical copy: the automatic
    path, forward and backward, must stay on the NHWC array."""
    monkeypatch.setattr(fused_bn_act, "_use_pallas", lambda: True)
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
    # the check can see one: the forced kernel reshapes to [rows, C]
    forced = jax.make_jaxpr(lambda x: bn_relu_residual(
        x, vec, vec, vec, vec, impl="pallas"))(x)
    assert activation_sized(forced.jaxpr, ("reshape",), x.size)


@pytest.mark.parametrize("with_z", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nhwc_xla_side_matches_reference_and_kernel(with_z, dtype):
    """Value and the six cotangents of the NHWC jnp epilogue (custom
    VJP, two reductions) against plain autodiff of
    ``bn_act_epilogue_ref`` and against the interpreted kernel."""
    x, z, mean, invstd, w, b = _operands(c=16, dtype=dtype, seed=6)
    zz = z if with_z else None
    nargs = 6 if with_z else 5

    def loss(fn, xx, mm, ii, ww, bb, z_=None):
        out = fn(xx, mm, ii, ww, bb, z=z_, relu=True)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    args = (x, mean, invstd, w, b) + ((zz,) if with_z else ())
    sides = {
        "xla": bn_relu_residual,
        "ref": bn_act_epilogue_ref,
        "kernel": functools.partial(bn_relu_residual, interpret=True),
    }
    got = {name: jax.value_and_grad(functools.partial(loss, fn),
                                    argnums=tuple(range(nargs)))(*args)
           for name, fn in sides.items()}
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    for other in ("ref", "kernel"):
        np.testing.assert_allclose(float(got["xla"][0]),
                                   float(got[other][0]), rtol=tol)
        for a, r in zip(got["xla"][1], got[other][1]):
            assert a.shape == r.shape and a.dtype == r.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(r, np.float32),
                                       atol=tol, rtol=tol, err_msg=other)


def _tiny_resnet(fused_epilogue):
    from apex_tpu.models import ResNet18
    return ResNet18(num_classes=10, dtype=jnp.float32, sync_bn=True,
                    fused_epilogue=fused_epilogue)


def test_resnet_norm_factory_fused_matches_explicit():
    """The block rewiring is routing, not math: a SyncBatchNorm ResNet
    with the fused chains must match the explicit relu/add statements
    on the SAME parameters — forward and grads."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
    m_fused, m_plain = _tiny_resnet(None), _tiny_resnet(False)
    variables = m_fused.init(jax.random.PRNGKey(0), x, train=True)
    # identical param/stat trees: the hook changes no module names
    v2 = m_plain.init(jax.random.PRNGKey(0), x, train=True)
    assert (jax.tree_util.tree_structure(variables)
            == jax.tree_util.tree_structure(v2))

    def fwd(model, p):
        y, upd = model.apply({"params": p,
                              "batch_stats": variables["batch_stats"]},
                             x, train=True, mutable=["batch_stats"])
        return jnp.sum(y ** 2), upd

    (y_f, upd_f), g_f = jax.value_and_grad(
        lambda p: fwd(m_fused, p), has_aux=True)(variables["params"])
    (y_p, upd_p), g_p = jax.value_and_grad(
        lambda p: fwd(m_plain, p), has_aux=True)(variables["params"])
    np.testing.assert_allclose(float(y_f), float(y_p), rtol=1e-6)
    for a, r in zip(jax.tree_util.tree_leaves(g_f),
                    jax.tree_util.tree_leaves(g_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-5, rtol=1e-4)
    for a, r in zip(jax.tree_util.tree_leaves(upd_f),
                    jax.tree_util.tree_leaves(upd_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-5, rtol=1e-4)


def test_resnet_fused_epilogue_requires_capable_norm():
    from apex_tpu.models import ResNet18

    model = ResNet18(num_classes=10, fused_epilogue=True)  # plain BN
    x = jnp.ones((1, 32, 32, 3))
    with pytest.raises(ValueError, match="fuse_relu"):
        model.init(jax.random.PRNGKey(0), x, train=True)


def test_resnet_groupbn_norm_cls_end_to_end():
    """The imagenet --fused-bn wiring: ResNet over
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
