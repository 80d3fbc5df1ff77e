"""``ops.apply_rope`` and ``ops.qk_norm_rope`` (rotary positions over the two
halves of a head, behind a per-head RMSNorm) against a complex-number rotation
in float64 written here: pair ``i`` of a head is the complex number ``x[i] + 1j
x[i + d/2]`` and turns by ``exp(1j pos theta^(-2i/d))``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import ops

THETA, EPS = 1e6, 1e-5


def _complex_rotation(x, positions, theta):
    x = np.asarray(x, np.float64)
    d = x.shape[-1]
    z = x[..., :d // 2] + 1j * x[..., d // 2:]
    freq = theta ** (-2.0 * np.arange(d // 2) / d)
    turn = np.exp(1j * np.asarray(positions, np.float64)[..., None, None] * freq)
    z = z * turn
    return np.concatenate([z.real, z.imag], axis=-1)


def _norm64(x, w):
    x = np.asarray(x, np.float64)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * np.asarray(
        w, np.float64)


def _inputs(t=37, heads=4, kv=2, d=16, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(t + d), 4)
    return (jax.random.normal(k[0], (2, t, heads, d)).astype(dtype),
            jax.random.normal(k[1], (2, t, kv, d)).astype(dtype),
            1 + 0.2 * jax.random.normal(k[2], (d,)),
            1 + 0.2 * jax.random.normal(k[3], (d,)))


@pytest.mark.parametrize("d", [2, 16, 64])
def test_rotation_is_the_complex_one(d):
    q, _, _, _ = _inputs(d=d)
    got = ops.apply_rope(q, theta=THETA)
    want = _complex_rotation(q, np.arange(q.shape[1]), THETA)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=1e-5)
    # position 0 is left where it was, and norms are kept
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(q[:, 0]))
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(q, axis=-1), rtol=1e-5)


def test_positions_far_out_keep_float32_angles():
    """At position 4,095 and beyond the angle of the first pair is thousands
    of radians: a rounded angle is another rotation."""
    q, _, _, _ = _inputs(t=8, d=64)
    positions = jnp.asarray([0, 1, 4095, 4096, 65535, 100000, 127999, 5])
    got = ops.apply_rope(q, positions, theta=THETA)
    want = _complex_rotation(q, positions, THETA)
    # float32 angles of up to 1.3e5 radians carry 8e-3 of absolute error
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=5e-2)
    np.testing.assert_allclose(np.asarray(got[:, :4], np.float64),
                               want[:, :4], atol=2e-3)
    per_batch = ops.apply_rope(q, jnp.stack([positions, positions]), theta=THETA)
    np.testing.assert_array_equal(np.asarray(per_batch), np.asarray(got))


def test_angles_and_an_odd_head_size():
    angles = ops.rope_angles(jnp.arange(5), 8, 10000.0)
    assert angles.shape == (5, 4) and angles.dtype == jnp.float32
    np.testing.assert_allclose(
        angles, np.arange(5)[:, None] * 10000.0 ** (-np.arange(4) / 4), rtol=1e-6)
    with pytest.raises(ValueError, match="odd"):
        ops.rope_angles(jnp.arange(5), 7)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6), (jnp.bfloat16, 2e-2)])
def test_norm_then_rotation_forward_and_gradients(dtype, tol):
    q, k, wq, wk = _inputs(dtype=dtype)
    got_q, got_k = ops.qk_norm_rope(q, k, wq, wk, theta=THETA, eps=EPS)
    pos = np.arange(q.shape[1])
    want_q = _complex_rotation(_norm64(q, wq), pos, THETA)
    want_k = _complex_rotation(_norm64(k, wk), pos, THETA)
    assert got_q.dtype == got_k.dtype == dtype
    np.testing.assert_allclose(np.asarray(got_q, np.float64), want_q, atol=4 * tol)
    np.testing.assert_allclose(np.asarray(got_k, np.float64), want_k, atol=4 * tol)
    if dtype != jnp.float32:
        return
    # gradients against autodiff of the norm followed by the rotation written
    # with an explicit rotation matrix per pair
    cq, ck = jax.random.normal(jax.random.PRNGKey(9), q.shape), jax.random.normal(
        jax.random.PRNGKey(8), k.shape)

    def plain(q, k, wq, wk):
        def turn(x, w):
            x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS) * w
            d = x.shape[-1]
            angle = (jnp.arange(x.shape[1])[:, None, None]
                     * THETA ** (-2.0 * jnp.arange(d // 2) / d))
            pair = jnp.stack([x[..., :d // 2], x[..., d // 2:]], -1)
            rot = jnp.stack([jnp.stack([jnp.cos(angle), -jnp.sin(angle)], -1),
                             jnp.stack([jnp.sin(angle), jnp.cos(angle)], -1)], -2)
            out = jnp.einsum("thpij,bthpj->bthpi", rot, pair)
            return jnp.concatenate([out[..., 0], out[..., 1]], -1)
        return turn(q, wq), turn(k, wk)

    scalar = lambda f: lambda *a: sum(jnp.sum(o * c) for o, c in zip(f(*a), (cq, ck)))
    ours = lambda *a: ops.qk_norm_rope(*a, theta=THETA, eps=EPS)
    got = jax.grad(scalar(ours), argnums=(0, 1, 2, 3))(q, k, wq, wk)
    want = jax.grad(scalar(plain), argnums=(0, 1, 2, 3))(q, k, wq, wk)
    for name, a, b in zip(("q", "k", "q weight", "k weight"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()),
                                   err_msg=name)
