"""``ops.gated_short_conv`` (``C * causal depthwise conv(B * x)``, a backward
rule of its own) against ``jnp.convolve`` per channel and autodiff of that:
output and the gradients of ``B``, ``C``, ``x`` and the taps, in float32 and
in bf16; the chunked form against the tokens-major one; what the backward pass
keeps; and that the conv shares its shifted views with ``causal_conv_silu``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # public only as a printer

from apex_tpu import ops
from apex_tpu.ops import short_conv, ssd

CHANNELS = 5


def plain(b, c, x, taps):
    """Per batch row and channel: ``convolve(b x, reversed taps)``, its first
    ``T`` values (token ``t`` reads ``t - W + 1 .. t``)."""
    t = x.shape[1]
    bx = (b.astype(jnp.float32) * x.astype(jnp.float32))
    one = lambda seq, w: jnp.convolve(seq, w[::-1])[:t]
    conv = jax.vmap(jax.vmap(one, in_axes=(1, 1), out_axes=1),
                    in_axes=(0, None))(bx, taps)
    return (c.astype(jnp.float32) * conv).astype(x.dtype)


def _inputs(t, w, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(10 * t + w), 5)
    draw = lambda key: jax.random.normal(key, (2, t, CHANNELS)).astype(dtype)
    return (draw(k[0]), draw(k[1]), draw(k[2]),
            jax.random.uniform(k[3], (w, CHANNELS), minval=-.6, maxval=.6),
            draw(k[4]))


def _value_and_grads(f, b, c, x, taps, cot):
    return f(b, c, x, taps), jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * cot.astype(jnp.float32)),
        argnums=(0, 1, 2, 3))(b, c, x, taps)


def _worst(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("w", [2, 3])
@pytest.mark.parametrize("t", [1, 2, 7, 64])
def test_float32_equals_convolve_per_channel(t, w):
    b, c, x, taps, cot = _inputs(t, w)
    got, got_grads = _value_and_grads(ops.gated_short_conv, b, c, x, taps, cot)
    want, want_grads = _value_and_grads(plain, b, c, x, taps, cot)
    assert got.shape == x.shape and got.dtype == x.dtype
    assert _worst(got, want) < 1e-6
    for name, a, g in zip(("B", "C", "x", "taps"), got_grads, want_grads):
        assert a.shape == g.shape and a.dtype == g.dtype, name
        assert _worst(a, g) < 2e-6, name


def test_bf16_is_float32_between_load_and_store():
    b, c, x, taps, cot = _inputs(64, 3, jnp.bfloat16)
    got, got_grads = _value_and_grads(ops.gated_short_conv, b, c, x, taps, cot)
    want, want_grads = _value_and_grads(plain, b, c, x, taps, cot)
    assert got.dtype == jnp.bfloat16
    assert _worst(got, want) < 1e-2
    for name, a, g in zip(("B", "C", "x", "taps"), got_grads, want_grads):
        assert a.dtype == g.dtype, name
        # dC is made from the conv as the forward kept it, in bf16
        assert _worst(a, g) < 2e-2, name


@pytest.mark.parametrize("q", [2, 4, 8])
def test_chunked_form_reads_across_chunk_edges(q):
    b, c, x, taps, cot = _inputs(16, 3)
    cut = lambda a: a.reshape(2, 16 // q, q, CHANNELS).transpose(0, 1, 3, 2)
    join = lambda a: a.transpose(0, 1, 3, 2).reshape(2, 16, CHANNELS)
    chunked = lambda b, c, x, taps: join(
        ops.gated_short_conv(cut(b), cut(c), cut(x), taps))
    got, got_grads = _value_and_grads(chunked, b, c, x, taps, cot)
    want, want_grads = _value_and_grads(plain, b, c, x, taps, cot)
    assert _worst(got, want) < 1e-6
    for a, g in zip(got_grads, want_grads):
        assert _worst(a, g) < 2e-6


def test_the_backward_keeps_the_conv_and_no_shifted_copy():
    """The rule's residuals are the three inputs, the taps and the conv's
    result in the compute dtype: nothing float32 of the activations' size."""
    b, c, x, taps, _ = _inputs(64, 3, jnp.bfloat16)
    kept = saved_residuals(
        lambda *a: jnp.sum(ops.gated_short_conv(*a).astype(jnp.float32)),
        b, c, x, taps)
    big = [aval for aval, _ in kept if aval.size >= x.size]
    assert len(big) == 4 and all(aval.dtype == jnp.bfloat16 for aval in big)


def test_it_shares_the_shifted_views_with_causal_conv_silu():
    assert short_conv._shift is ssd._shift
    assert short_conv._taps_views is ssd._taps_views


def test_shapes_and_dtypes_must_agree():
    b, c, x, taps, _ = _inputs(8, 3)
    with pytest.raises(ValueError, match="must agree"):
        ops.gated_short_conv(b[:, :4], c, x, taps)
    with pytest.raises(ValueError, match="must agree"):
        ops.gated_short_conv(b.astype(jnp.bfloat16), c, x, taps)
