"""RMSNorm and the gated RMSNorm against a float32 jnp norm written here:
forward and gradients, float32 and bf16 inputs, function and module."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import RMSNorm, gated_rms_norm, rms_norm

EPS = 1e-5


def _plain(x, w, z=None):
    x = x.astype(jnp.float32)
    if z is not None:
        x = x * (z.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32)))
    return x / jnp.sqrt(jnp.mean(x ** 2, axis=-1, keepdims=True) + EPS) * w


def _inputs(dtype, shape=(3, 5, 64)):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(k[0], shape, dtype) * 3,
            1 + 0.1 * jax.random.normal(k[1], shape[-1:]),
            jax.random.normal(k[2], shape, dtype),
            jax.random.normal(k[3], shape))


@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_forward_and_gradients(dtype, tol, gated):
    x, w, z, cot = _inputs(dtype)
    ours = (lambda x, w, z: gated_rms_norm(x, z, w, EPS)) if gated else (
        lambda x, w, z: rms_norm(x, w, EPS))
    plain = lambda x, w, z: _plain(x, w, z if gated else None)
    y = ours(x, w, z)
    assert y.dtype == dtype and y.shape == x.shape
    np.testing.assert_allclose(np.asarray(y, np.float32), plain(x, w, z),
                               atol=tol * 3, rtol=tol)
    loss = lambda f: lambda x, w, z: jnp.sum(f(x, w, z).astype(jnp.float32) * cot)
    got = jax.grad(loss(ours), argnums=(0, 1, 2))(x, w, z)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(x, w, z)
    for g, r in zip(got[:3 if gated else 2], want):
        assert g.dtype == r.dtype
        err = np.linalg.norm(np.asarray(g, np.float32) - np.asarray(r, np.float32))
        assert err <= tol * np.linalg.norm(np.asarray(r, np.float32))


def test_statistics_are_float32_whatever_the_input():
    # 300 squared overflows nothing in bf16, but its mean over 4096 values
    # is read to 3 digits there; the float32 statistics give 1 exactly
    x = jnp.full((2, 4096), 300.0, jnp.bfloat16)
    assert float(jnp.abs(rms_norm(x).astype(jnp.float32) - 1).max()) < 1e-2


def test_module_creates_a_float32_scale_of_ones():
    x, _, z, _ = _inputs(jnp.bfloat16)
    module = RMSNorm(EPS)
    params = module.init(jax.random.PRNGKey(0), x)["params"]
    assert params["scale"].dtype == jnp.float32
    np.testing.assert_array_equal(params["scale"], np.ones(64, np.float32))
    assert module.apply({"params": params}, x).dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        module.apply({"params": params}, x, gate=z),
        gated_rms_norm(x, z, params["scale"], EPS))


@pytest.mark.parametrize("axis", [-1, 1])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_the_gated_norms_two_factors_multiply_to_it(dtype, tol, axis):
    """``gated_rms_norm_factors``: ``x silu(z) w`` in ``x``'s dtype and the
    float32 factor of each row; their product is the gated norm, value and
    gradients, over the last axis and over one in the middle."""
    from apex_tpu.normalization import gated_rms_norm_factors

    x, w, z, cot = _inputs(dtype)
    if axis != -1:
        x, z, cot = (jnp.moveaxis(a, -1, axis) for a in (x, z, cot))

    def product(x, w, z):
        gated, inv_rms = gated_rms_norm_factors(x, z, w, EPS, axis=axis)
        assert gated.dtype == x.dtype and inv_rms.dtype == jnp.float32
        assert inv_rms.shape[axis] == 1
        return gated.astype(jnp.float32) * inv_rms

    plain = lambda x, w, z: jnp.moveaxis(_plain(
        jnp.moveaxis(x, axis, -1), w, jnp.moveaxis(z, axis, -1)), -1, axis)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * cot)
    got = product(x, w, z)
    want = plain(x, w, z)
    assert float(jnp.abs(got - want).max()) < tol * float(jnp.abs(want).max())
    for a, b in zip(jax.grad(loss(product), argnums=(0, 1, 2))(x, w, z),
                    jax.grad(loss(plain), argnums=(0, 1, 2))(x, w, z)):
        scale = float(jnp.abs(b.astype(jnp.float32)).max())
        assert float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max()) < 2 * tol * scale
