"""Pallas NHWC implicit-GEMM conv (ISSUE 18): interpret-mode parity vs
the ``lax.conv_general_dilated`` oracle, fused-epilogue equivalence on a
real ResNet block, tune-dispatch bitwise parity, and the zero-retrace
warmup pin.  ``interpret=True`` runs the REAL kernels on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import conv as conv_mod
from apex_tpu.ops.conv import (conv2d, conv2d_ref, PallasConv,
                               conv_dispatch_stats,
                               reset_conv_dispatch_stats)
from apex_tpu.normalization.fused_bn_act import bn_act_epilogue_ref
from apex_tpu.prof import assert_trace_count
from test_fused_bn_act import CHIP_SMOKE


def _mk(rs, *shape, dtype=jnp.float32):
    return jnp.asarray(rs.randn(*shape), jnp.float32).astype(dtype)


# -- forward / backward parity vs the oracle ----------------------------------

_MATRIX = [
    # (x_shape, w_shape, stride, padding, dilation)
    ((2, 8, 8, 16), (3, 3, 16, 32), 1, "SAME", 1),      # the stage conv
    ((2, 9, 7, 8), (3, 3, 8, 16), 2, "SAME", 1),        # odd + stride
    ((2, 8, 8, 8), (1, 1, 8, 16), 1, "VALID", 1),       # pointwise
    ((2, 8, 8, 8), (1, 1, 8, 16), 2, "VALID", 1),       # strided 1x1
    ((2, 12, 12, 8), (3, 3, 8, 16), 1, "VALID", 2),     # dilated
    ((1, 14, 14, 8), (7, 7, 8, 16), 2, ((3, 3), (3, 3)), 1),  # stem-like
]


@pytest.mark.parametrize("case", range(len(_MATRIX)))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interpret_forward_parity(case, dtype):
    xs, ws, s, p, d = _MATRIX[case]
    rs = np.random.RandomState(case)
    x, w = _mk(rs, *xs, dtype=dtype), _mk(rs, *ws, dtype=dtype)
    out = conv2d(x, w, stride=s, padding=p, dilation=d, interpret=True)
    ref = conv2d_ref(x, w, stride=s, padding=p, dilation=d)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", range(len(_MATRIX)))
def test_interpret_dgrad_wgrad_parity(case):
    """The custom-VJP backward (dgrad via rotated-weight forward
    machinery, wgrad via the dedicated accumulation kernel) against
    jax's autodiff of the oracle."""
    xs, ws, s, p, d = _MATRIX[case]
    rs = np.random.RandomState(10 + case)
    x, w = _mk(rs, *xs), _mk(rs, *ws)

    def loss_k(x, w):
        return jnp.sum(jnp.sin(conv2d(x, w, stride=s, padding=p,
                                      dilation=d, interpret=True)))

    def loss_r(x, w):
        return jnp.sum(jnp.sin(conv2d_ref(x, w, stride=s, padding=p,
                                          dilation=d)))

    gx, gw = jax.grad(loss_k, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_r, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-4, atol=1e-4)


# -- fused epilogue -----------------------------------------------------------

def _epilogue_operands(rs, o, n, oh, ow, with_z):
    mean = _mk(rs, o)
    invstd = jnp.abs(_mk(rs, o)) + 0.5
    scale, bias = _mk(rs, o), _mk(rs, o)
    z = _mk(rs, n, oh, ow, o) if with_z else None
    return mean, invstd, scale, bias, z


@pytest.mark.parametrize("with_z", [False, True])
def test_fused_epilogue_matches_explicit_chain(with_z):
    """conv+bn+relu(+z) in ONE kernel vs conv kernel then the epilogue
    reference — forward and every cotangent (x, w, mean, invstd, scale,
    bias, z).  Same conv feeds both sides, so only instruction-fusion
    epsilon separates them (the test_fused_bn_act tolerance)."""
    rs = np.random.RandomState(3)
    x, w = _mk(rs, 2, 8, 8, 16), _mk(rs, 3, 3, 16, 32)
    mean, invstd, scale, bias, z = _epilogue_operands(rs, 32, 2, 8, 8,
                                                      with_z)
    ep = (mean, invstd, scale, bias) + ((z,) if with_z else ())

    def fused(x, w, mean, invstd, scale, bias, z=None):
        return jnp.sum(jnp.sin(conv2d(
            x, w, mean=mean, invstd=invstd, scale=scale, bias=bias, z=z,
            relu=True, interpret=True)))

    def chain(x, w, mean, invstd, scale, bias, z=None):
        y = conv2d(x, w, interpret=True)
        return jnp.sum(jnp.sin(bn_act_epilogue_ref(
            y, mean, invstd, scale, bias, z, True)))

    args = (x, w) + ep
    nargs = len(args)
    f = fused(*args)
    c = chain(*args)
    np.testing.assert_allclose(float(f), float(c), rtol=1e-5, atol=1e-4)
    gf = jax.grad(fused, argnums=tuple(range(nargs)))(*args)
    gc = jax.grad(chain, argnums=tuple(range(nargs)))(*args)
    for a, r in zip(gf, gc):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


def test_epilogue_argument_validation():
    x, w = jnp.ones((1, 4, 4, 8)), jnp.ones((3, 3, 8, 8))
    with pytest.raises(ValueError, match="together"):
        conv2d(x, w, mean=jnp.zeros(8))
    with pytest.raises(ValueError, match="epilogue"):
        conv2d(x, w, relu=True)
    with pytest.raises(ValueError, match="together"):
        conv2d(x, w, mean=jnp.zeros(8), invstd=jnp.ones(8),
               scale=jnp.ones(8))
    with pytest.raises(ValueError, match="output shape"):
        conv2d(x, w, mean=jnp.zeros(8), invstd=jnp.ones(8),
               z=jnp.ones((1, 2, 2, 8)))


# -- ResNet block via the conv_cls hook ---------------------------------------

def _tiny_resnet(conv_cls):
    from apex_tpu.models import ResNet18
    return ResNet18(num_classes=10, dtype=jnp.float32, sync_bn=True,
                    conv_cls=conv_cls)


def test_resnet_conv_cls_matches_nn_conv():
    """The conv_cls= hook is routing, not math: a PallasConv ResNet has
    the IDENTICAL param/stat pytree (same checkpoint) and matches the
    nn.Conv model's forward, grads, and BN stats on the same params."""
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(2, 32, 32, 3), jnp.float32)
    m_pallas, m_plain = _tiny_resnet(PallasConv), _tiny_resnet(None)
    variables = m_pallas.init(jax.random.PRNGKey(0), x, train=True)
    v2 = m_plain.init(jax.random.PRNGKey(0), x, train=True)
    assert (jax.tree_util.tree_structure(variables)
            == jax.tree_util.tree_structure(v2))
    for a, b in zip(jax.tree_util.tree_leaves(variables),
                    jax.tree_util.tree_leaves(v2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def fwd(model, p):
        y, upd = model.apply({"params": p,
                              "batch_stats": variables["batch_stats"]},
                             x, train=True, mutable=["batch_stats"])
        return jnp.sum(y ** 2), upd

    (y_a, upd_a), g_a = jax.value_and_grad(
        lambda p: fwd(m_pallas, p), has_aux=True)(variables["params"])
    (y_b, upd_b), g_b = jax.value_and_grad(
        lambda p: fwd(m_plain, p), has_aux=True)(variables["params"])
    np.testing.assert_allclose(float(y_a), float(y_b), rtol=1e-6)
    for a, r in zip(jax.tree_util.tree_leaves(g_a),
                    jax.tree_util.tree_leaves(g_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-5, rtol=1e-4)
    for a, r in zip(jax.tree_util.tree_leaves(upd_a),
                    jax.tree_util.tree_leaves(upd_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=1e-5, rtol=1e-4)


def test_depthwise_falls_back_and_is_counted():
    """Grouped/depthwise convs are outside the kernel's contract: the
    module routes them to XLA per site and the stats name the reason."""
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(2, 8, 8, 16), jnp.float32)
    reset_conv_dispatch_stats()
    m = PallasConv(features=16, kernel_size=(3, 3), feature_group_count=16,
                   use_bias=False)
    import flax.linen as nn
    ref = nn.Conv(features=16, kernel_size=(3, 3), feature_group_count=16,
                  use_bias=False)
    v = m.init(jax.random.PRNGKey(0), x)
    vr = ref.init(jax.random.PRNGKey(0), x)
    np.testing.assert_allclose(np.asarray(m.apply(v, x)),
                               np.asarray(ref.apply(vr, x)),
                               rtol=1e-5, atol=1e-5)
    stats = conv_dispatch_stats()
    assert stats["fallback_sites"] >= 1
    assert stats["fallback_reasons"].get("groups", 0) >= 1
    reset_conv_dispatch_stats()


# -- dispatch & tuning --------------------------------------------------------

def test_dispatch_gates(monkeypatch):
    x, w = jnp.ones((1, 4, 4, 8)), jnp.ones((3, 3, 8, 8))
    with pytest.raises(ValueError, match="impl"):
        conv2d(x, w, impl="bogus")
    # off-TPU, impl="pallas" still routes to the jnp reference (the
    # TPU gate wins) — same shape/result, no crash
    out = conv2d(x, w, impl="pallas")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(conv2d_ref(x, w)))
    assert not conv_mod._dispatch_pallas("pallas", True)
    # on the TPU the kernel runs only when forced and able
    monkeypatch.setattr(conv_mod, "_use_pallas", lambda: True)
    assert conv_mod._dispatch_pallas("pallas", True)
    assert not conv_mod._dispatch_pallas("pallas", False)
    assert not conv_mod._dispatch_pallas(None, True)
    assert not conv_mod._dispatch_pallas("jnp", True)


@pytest.mark.parametrize("h,cin,cout,k", CHIP_SMOKE.RESNET50_CONVS)
def test_resnet50_site_takes_xla_unless_forced(h, cin, cout, k, monkeypatch):
    """Every stride-1 conv site of the ResNet-50 step at b256, bf16, as
    if on the TPU.  The kernel can serve each (no capability gate
    answers first), the automatic dispatch still takes XLA's conv with
    the padding in the conv and no ``pad`` before it, and
    ``impl="pallas"`` is still the kernel (PERF.md section 6, PR 26)."""
    monkeypatch.setattr(conv_mod, "_use_pallas", lambda: True)
    x_shape, w_shape = (256, h, h, cin), (k, k, cin, cout)
    padding = conv_mod._norm_padding("SAME", h, h, k, k, 1, 1, 1, 1)
    assert conv_mod._site_reason(x_shape, w_shape, padding, (1, 1), (1, 1),
                                 1, 2) == "xla"

    x = jax.ShapeDtypeStruct(x_shape, jnp.bfloat16)
    w = jax.ShapeDtypeStruct(w_shape, jnp.bfloat16)
    reset_conv_dispatch_stats()
    module = PallasConv(features=cout, kernel_size=(k, k), use_bias=False,
                        dtype=jnp.bfloat16)
    auto = jax.make_jaxpr(
        lambda x, w: module.apply({"params": {"kernel": w}}, x))(x, w)
    assert conv_dispatch_stats()["fallback_reasons"] == {"xla": 1}
    reset_conv_dispatch_stats()
    assert [e.primitive.name for e in auto.jaxpr.eqns] == [
        "conv_general_dilated"]         # no pad, no cast: as nn.Conv runs it
    forced = str(jax.make_jaxpr(
        lambda x, w: conv2d(x, w, impl="pallas"))(x, w))
    assert "pallas_call" in forced


def test_tuned_blocks_match_default_bitwise():
    """The tune sweep's correctness premise (exact=True in the
    registry): block partitioning never reorders an output element's
    tap/K reduction, so ANY legal (block_m, block_n) is bitwise equal
    to the defaults in fp32."""
    rs = np.random.RandomState(6)
    x, w = _mk(rs, 2, 10, 10, 16), _mk(rs, 3, 3, 16, 32)
    mean, invstd, scale, bias, z = _epilogue_operands(rs, 32, 2, 10, 10,
                                                      True)
    kw = dict(mean=mean, invstd=invstd, scale=scale, bias=bias, z=z,
              relu=True, interpret=True)
    base = conv2d(x, w, **kw)
    for bm, bn in ((128, 128), (256, 512), (1024, 128)):
        tuned = conv2d(x, w, block_m=bm, block_n=bn, **kw)
        assert np.array_equal(np.asarray(base), np.asarray(tuned)), \
            (bm, bn)


def test_zero_retrace_after_warmup():
    """One compile on warmup, zero on steady-state repeats — the
    trace-count pin behind the StepPipeline.warmup acceptance."""
    rs = np.random.RandomState(7)
    x, w = _mk(rs, 2, 8, 8, 16), _mk(rs, 3, 3, 16, 32)
    mean, invstd, scale, bias, z = _epilogue_operands(rs, 32, 2, 8, 8,
                                                      True)

    @jax.jit
    def step(x, w, mean, invstd, scale, bias, z):
        out, grads = jax.value_and_grad(
            lambda x, w: jnp.sum(conv2d(x, w, mean=mean, invstd=invstd,
                                        scale=scale, bias=bias, z=z,
                                        relu=True, interpret=True) ** 2),
            argnums=(0, 1))(x, w)
        return out, grads

    with assert_trace_count(step, 1):
        step(x, w, mean, invstd, scale, bias, z)
    with assert_trace_count(step, 0):
        for _ in range(3):
            step(x, w, mean, invstd, scale, bias, z)
