"""The chunked state-space scan (``ops.ssd_scan``) against the recurrence run
token by token, forward and every gradient, for lengths that do and do not
divide into chunks, several chunk sizes and group counts, and decays small
enough to underflow in bf16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import ops


def sequential(x, dt, A, B, C, D):
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    B, C = jnp.repeat(B, h // g, axis=2), jnp.repeat(C, h // g, axis=2)

    def one(x, dt, B, C):
        def step(S, inputs):
            x_t, dt_t, B_t, C_t = inputs
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            return S, (S * C_t[:, None, :]).sum(-1) + D[:, None] * x_t
        return jax.lax.scan(step, jnp.zeros((h, p, n)), (x, dt, B, C))[1]
    return jax.vmap(one)(x, dt, B, C)


def _inputs(t, groups, dt_shift=-1.0, a_scale=1.0, seed=0):
    b, h, p, n = 2, 4, 8, 16
    k = jax.random.split(jax.random.PRNGKey(seed + t), 6)
    return (jax.random.normal(k[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) * 2 + dt_shift),
            -jnp.exp(jax.random.normal(k[2], (h,)) * 1.5 + 1) * a_scale,
            jax.random.normal(k[3], (b, t, groups, n)),
            jax.random.normal(k[4], (b, t, groups, n)),
            jax.random.normal(k[5], (h,)))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


@pytest.mark.parametrize("t,chunk,groups", [
    (24, 8, 1), (37, 16, 2), (64, 64, 1), (40, 256, 1), (96, 32, 4),
    (128, 16, 1)])
def test_chunked_equals_sequential_forward_and_gradients(t, chunk, groups):
    args = _inputs(t, groups)
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    chunked = lambda *a: ops.ssd_scan(*a, chunk=chunk)
    assert _rel(chunked(*args), sequential(*args)) < 2e-5
    grads = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                               argnums=tuple(range(6)))(*args)
    for name, got, want in zip("x dt A B C D".split(), grads(chunked),
                               grads(sequential)):
        assert _rel(got, want) < 2e-4, name


def test_decays_that_underflow_in_bf16():
    """Steps of 1 to 20 times ``A`` down to -60: over a chunk the running sum
    passes -10,000, a decay between neighbours is as small as exp(-1000).
    In bf16 the sum would be read to 3 digits and every decay inside a chunk
    would be wrong; here they are float32 and the result is the recurrence's,
    finite in value and gradient."""
    args = _inputs(64, 1, dt_shift=3.0, a_scale=8.0)
    assert float(jnp.min(args[1].max() * args[2])) < -500
    chunked = lambda *a: ops.ssd_scan(*a, chunk=32)
    assert _rel(chunked(*args), sequential(*args)) < 2e-5
    grads = jax.grad(lambda *a: jnp.sum(chunked(*a) ** 2),
                     argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(sequential(*a) ** 2),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for got, ref in zip(grads, want):
        assert np.isfinite(np.asarray(got)).all()
        # a float32 running sum near 10,000 resolves 1e-3, and so do the
        # decays that are differences of it
        assert _rel(got, ref) < 2e-3
    # the same products with bf16 operands keep the float32 decays: within
    # bf16's rounding of the operands, not of the running sums
    lowp = [a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
            for i, a in enumerate(args)]
    exact = sequential(*[a.astype(jnp.float32) for a in lowp])
    assert chunked(*lowp).dtype == jnp.bfloat16
    assert _rel(chunked(*lowp), exact) < 2e-2


def test_the_chunk_size_changes_no_result_and_the_program_is_plain_xla():
    """The chunk is how the sum is split, not what it is; and there is one
    implementation, with no kernel of its own behind it."""
    args = _inputs(48, 2)
    whole = ops.ssd_scan(*args, chunk=48)
    for chunk in (8, 16, 32):
        assert _rel(ops.ssd_scan(*args, chunk=chunk), whole) < 2e-5, chunk
    lowered = jax.jit(lambda *a: ops.ssd_scan(*a, chunk=16)).lower(*args)
    assert "custom_call" not in lowered.as_text()


def test_shapes_that_do_not_fit_are_refused():
    x, dt, A, B, C, D = _inputs(16, 1)
    with pytest.raises(ValueError):
        ops.ssd_scan(x, dt, A, jnp.concatenate([B] * 3, axis=2), C, D)
    with pytest.raises(ValueError):
        ops.ssd_scan(x, dt[:, :8], A, B, C, D)
