"""``ops.causal_conv_silu`` (the Mamba-2 mixer's depthwise conv of ``W`` taps,
bias and SiLU, written for chunked arrays with a backward pass of its own)
against the plain formulation: pad, ``W`` shifted products, bias, SiLU, and
autodiff of that.  Output and the gradients of ``x``, taps and bias, in
float32 and in bf16; the chunked form against the tokens-major one; and
what the backward pass keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # public only as a printer

from apex_tpu import ops
from apex_tpu.models import granite_hybrid
from apex_tpu.ops import ssd

CHANNELS = 5


def plain(x, taps, bias):
    w, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + t] * taps[k] for k in range(w))
    return jax.nn.silu(conv + bias).astype(x.dtype)


def _inputs(t, w, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(10 * t + w), 4)
    # the cotangent is rounded like the output, so both sides are handed
    # the same one
    return (jax.random.normal(k[0], (2, t, CHANNELS)).astype(dtype),
            jax.random.uniform(k[1], (w, CHANNELS), minval=-.5, maxval=.5),
            jax.random.normal(k[2], (CHANNELS,)) * .3,
            jax.random.normal(k[3], (2, t, CHANNELS)).astype(dtype))


def _value_and_grads(f, x, taps, bias, cot):
    return f(x, taps, bias), jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) * cot.astype(jnp.float32)),
        argnums=(0, 1, 2))(x, taps, bias)


def _worst(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("w", [2, 4])
@pytest.mark.parametrize("t", [1, 3, 7, 64])
def test_float32_equals_the_plain_formulation(t, w):
    x, taps, bias, cot = _inputs(t, w)
    got, got_grads = _value_and_grads(ops.causal_conv_silu, x, taps, bias, cot)
    want, want_grads = _value_and_grads(plain, x, taps, bias, cot)
    assert got.shape == x.shape and got.dtype == x.dtype
    assert _worst(got, want) < 1e-6
    for name, a, b in zip(("x", "taps", "bias"), got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _worst(a, b) < 1e-6, name


@pytest.mark.parametrize("w", [2, 4])
@pytest.mark.parametrize("t", [1, 3, 7, 64])
def test_bf16_is_the_float32_result_rounded_once(t, w):
    """bf16 in, bf16 out, float32 in between: against the plain formulation
    in float32 on the same (bf16-valued) inputs, the output and ``dx`` are
    within bf16's rounding (2**-8 of the largest) and the float32 sums for
    the taps and the bias are the same sums."""
    x, taps, bias, cot = _inputs(t, w, jnp.bfloat16)
    got, got_grads = _value_and_grads(ops.causal_conv_silu, x, taps, bias, cot)
    want, want_grads = _value_and_grads(
        plain, x.astype(jnp.float32), taps, bias, cot)
    assert got.dtype == jnp.bfloat16 and got_grads[0].dtype == jnp.bfloat16
    assert got_grads[1].dtype == got_grads[2].dtype == jnp.float32
    assert _worst(got, want) < 2.0 ** -8
    assert _worst(got_grads[0], want_grads[0]) < 2.0 ** -8
    assert _worst(got_grads[1], want_grads[1]) < 1e-5
    assert _worst(got_grads[2], want_grads[2]) < 1e-5


@pytest.mark.parametrize("t,q,w", [(64, 8, 4), (64, 16, 4), (64, 16, 2),
                                   (12, 4, 4), (12, 3, 4), (6, 1, 2)])
def test_chunked_equals_tokens_major(t, q, w):
    """``[batch, chunks, channels, Q]``: a chunk's first ``W - 1`` tokens
    read the chunk before, forward, and its last ones feed the chunk after,
    backward."""
    x, taps, bias, cot = _inputs(t, w)
    cut = lambda a: a.reshape(2, t // q, q, CHANNELS).transpose(0, 1, 3, 2)
    join = lambda a: a.transpose(0, 1, 3, 2).reshape(2, t, CHANNELS)
    chunked = lambda x, taps, bias: join(ops.causal_conv_silu(cut(x), taps, bias))
    got, got_grads = _value_and_grads(chunked, x, taps, bias, cot)
    want, want_grads = _value_and_grads(plain, x, taps, bias, cot)
    assert _worst(got, want) < 1e-6
    for name, a, b in zip(("x", "taps", "bias"), got_grads, want_grads):
        assert _worst(a, b) < 1e-6, name


def test_chunks_shorter_than_the_taps_reach_are_refused():
    x, taps, bias, _ = _inputs(8, 4)
    with pytest.raises(ValueError, match="spans more than a chunk"):
        ops.causal_conv_silu(x.reshape(2, 4, 2, CHANNELS).transpose(0, 1, 3, 2),
                             taps, bias)


def test_the_backward_keeps_no_float32_array_of_the_inputs_size():
    """The residuals are ``x`` in its own dtype, the taps and the bias: the
    rule's own tuple, and what autodiff reports as saved, for the conv alone
    and inside the mixer (where the scan keeps float32 arrays of its own,
    none of them of xBC's shape)."""
    x, taps, bias, _ = _inputs(64, 4, jnp.bfloat16)
    x4 = x.transpose(0, 2, 1)[:, None]
    _, res = ssd._conv_fwd(x4, taps, bias)
    assert [(r.shape, r.dtype) for r in res] == [
        (x4.shape, jnp.bfloat16), (taps.shape, jnp.float32),
        (bias.shape, jnp.float32)]
    saved = saved_residuals(ops.causal_conv_silu, x, taps,
                                              bias)
    assert not [aval for aval, _ in saved
                if aval.dtype == jnp.float32 and aval.size >= x.size]

    mixer = granite_hybrid.Mamba2Mixer(
        num_heads=8, head_dim=16, state_size=16, chunk_size=16,
        dtype=jnp.bfloat16)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32), jnp.bfloat16)
    params = mixer.init(jax.random.PRNGKey(1), h)
    d_conv = 8 * 16 + 2 * 16
    saved = saved_residuals(
        lambda p, h: mixer.apply(p, h).astype(jnp.float32).sum(), params, h)
    of_xbc = [aval for aval, _ in saved
              if d_conv in aval.shape and aval.size >= 2 * 64 * d_conv]
    assert of_xbc and all(aval.dtype == jnp.bfloat16 for aval in of_xbc)
