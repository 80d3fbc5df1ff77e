"""On-chip Pallas kernel tests (the CPU-pinned suite only ever exercises
the jnp fallbacks and the Pallas interpreter).

Run with ``APEX_TPU_TESTS=1 python -m pytest tests/test_pallas_tpu.py`` on
a TPU host (from the sandbox: through ``chiprun``): the ``tpu``-marked
tests below execute the Mosaic kernels directly and compare them against
the jnp oracle paths — the fallback-vs-kernel testing strategy of reference
``tests/L0/run_fused_layer_norm`` and
``apex/contrib/test/test_label_smoothing.py:10-28``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tpu


def _tpu_dev():
    return jax.devices("tpu")[0]


# -- FusedLayerNorm kernels ---------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (300, 768), (257, 1024)])
def test_layer_norm_pallas_fwd_matches_oracle(dtype, shape):
    from apex_tpu.normalization.fused_layer_norm import _fwd_ref, _pallas_fwd

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(rng.rand(shape[1]) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(shape[1]), jnp.float32)

    with jax.default_device(_tpu_dev()):
        out_k, mean_k, invvar_k = jax.jit(
            lambda x, w, b: _pallas_fwd(x, w, b, 1e-5))(x, w, b)
    out_r, mean_r, invvar_r = _fwd_ref(x, w, b, 1e-5)

    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(mean_k), np.asarray(mean_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(invvar_k), np.asarray(invvar_r),
                               atol=1e-3, rtol=1e-3)


def test_layer_norm_pallas_bwd_matches_oracle():
    from apex_tpu.normalization.fused_layer_norm import (
        _bwd_input_ref, _fwd_ref, _pallas_bwd_input)

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(64, 512), jnp.float32)
    w = jnp.asarray(rng.rand(512) + 0.5, jnp.float32)
    g = jnp.asarray(rng.randn(64, 512), jnp.float32)
    _, mean, invvar = _fwd_ref(x, w, None, 1e-5)

    with jax.default_device(_tpu_dev()):
        dx_k = jax.jit(lambda g, x, m, iv, w:
                       _pallas_bwd_input(g, x, m, iv, w))(g, x, mean,
                                                          invvar, w)
    dx_r = _bwd_input_ref(g, x, mean, invvar, w)
    np.testing.assert_allclose(np.asarray(dx_k), np.asarray(dx_r),
                               atol=1e-4, rtol=1e-4)


def test_layer_norm_end_to_end_grad_on_chip():
    """Full custom-VJP path under jit on the TPU default device."""
    from apex_tpu.normalization.fused_layer_norm import (_use_pallas,
                                                         fused_layer_norm)

    with jax.default_device(_tpu_dev()):
        assert _use_pallas(), "pallas path must be active on chip"
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(32, 256), jnp.float32)
        w = jnp.ones((256,), jnp.float32)
        b = jnp.zeros((256,), jnp.float32)

        # impl="pallas": (32, 256) is below the auto-dispatch crossover,
        # and THIS test exists to exercise the kernel VJP on chip.
        def loss(x, w, b):
            return jnp.sum(fused_layer_norm(x, 256, w, b, impl="pallas") ** 2)

        gx, gw, gb = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)

    import os
    os.environ["APEX_TPU_DISABLE_PALLAS"] = "1"
    try:
        gx_r, gw_r, gb_r = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)
    finally:
        del os.environ["APEX_TPU_DISABLE_PALLAS"]
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_r),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r),
                               atol=1e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_r),
                               atol=1e-2, rtol=1e-3)


# -- xentropy kernels ---------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 512), (2048, 30522)])
def test_xentropy_pallas_fwd_matches_oracle(shape):
    """Includes the LM-vocab shape that OOM'd VMEM before row-block sizing."""
    from apex_tpu.contrib.xentropy import _fwd_pallas, _fwd_ref

    n, h = shape
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(n, h), jnp.float32)
    labels = jnp.asarray(rng.randint(0, h, (n,)), jnp.int32)

    with jax.default_device(_tpu_dev()):
        loss_k, mlse_k = jax.jit(
            lambda l, y: _fwd_pallas(l, y, 0.1))(logits, labels)
    loss_r, mlse_r = _fwd_ref(logits, labels, 0.1)
    np.testing.assert_allclose(np.asarray(loss_k), np.asarray(loss_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(mlse_k), np.asarray(mlse_r),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [(256, 1000), (8192, 50257),
                                   (16384, 16384)])
def test_xentropy_pallas_bwd_matches_oracle(shape):
    """The compiled forward kernel under differentiation: its losses, and
    ``g * r`` against ``_bwd_ref`` at ResNet's head, the GPT cells' and the
    LFM2 and Nemotron cells'.  The big shapes are made and compared on the
    device (1.65 GB a logit matrix)."""
    from apex_tpu.contrib.xentropy import (_bwd_ref, _fwd_grad_pallas,
                                           _fwd_ref)

    n, h = shape

    def both(key):
        k1, k2, k3 = jax.random.split(key, 3)
        logits = jax.random.normal(k1, (n, h), jnp.float32) * 3.0
        labels = jax.random.randint(k2, (n,), 0, h, jnp.int32)
        g = jax.random.uniform(k3, (n,), jnp.float32)
        loss_r, mlse = _fwd_ref(logits, labels, 0.1)
        dx_r = _bwd_ref(g, logits, mlse, labels, 0.1)
        loss_k, r = _fwd_grad_pallas(logits, labels, 0.1)
        dx_k = g[:, None] * r
        return (jnp.max(jnp.abs(loss_k - loss_r)),
                jnp.max(jnp.abs(dx_k - dx_r)), jnp.sum(dx_k != dx_r))

    with jax.default_device(_tpu_dev()):
        loss_err, dx_err, differing = jax.jit(both)(jax.random.PRNGKey(4))
    assert float(loss_err) <= 1e-4
    assert float(dx_err) <= 1e-5, int(differing)


def test_xentropy_end_to_end_grad_on_chip():
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

    with jax.default_device(_tpu_dev()):
        rng = np.random.RandomState(5)
        logits = jnp.asarray(rng.randn(64, 128), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 128, (64,)), jnp.int32)
        labels = labels.at[0].set(0)   # exercise padding_idx masking

        def loss(l):
            return jnp.sum(softmax_cross_entropy_loss(l, labels,
                                                      smoothing=0.1,
                                                      padding_idx=0))
        val_k = jax.jit(loss)(logits)
        grad_k = jax.jit(jax.grad(loss))(logits)

    import os
    os.environ["APEX_TPU_DISABLE_PALLAS"] = "1"
    try:
        val_r = jax.jit(loss)(logits)
        grad_r = jax.jit(jax.grad(loss))(logits)
    finally:
        del os.environ["APEX_TPU_DISABLE_PALLAS"]
    np.testing.assert_allclose(float(val_k), float(val_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad_k), np.asarray(grad_r),
                               atol=1e-5, rtol=1e-4)
    # padded row contributes zero gradient
    assert np.allclose(np.asarray(grad_k)[0], 0.0)

# -- flash attention kernels --------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_fwd_on_chip(causal, dtype):
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(6)
    B, T, H, D = 2, 512, 4, 64
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), dtype) for _ in range(3))

    with jax.default_device(_tpu_dev()):
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=256, block_k=256))(q, k, v)
    ref = dot_product_attention(q.astype(jnp.float32),
                                k.astype(jnp.float32),
                                v.astype(jnp.float32), causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)


def test_flash_attention_bias_on_chip():
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(7)
    B, T, H, D = 2, 384, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    valid = jnp.arange(T)[None, :] < jnp.array([300, 128])[:, None]
    kb = jnp.where(valid, 0.0, -1e9)

    with jax.default_device(_tpu_dev()):
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, key_padding_bias=kb, block_q=128, block_k=128))(q, k, v)
    ref = dot_product_attention(q, k, v, bias=kb[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads_on_chip(causal):
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(8)
    B, T, H, D = 1, 256, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    with jax.default_device(_tpu_dev()):
        g_k = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128)),
            argnums=(0, 1, 2)))(q, k, v)
    g_r = jax.grad(loss(lambda q, k, v: dot_product_attention(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_flash_attention_dynamic_offsets_on_chip():
    """The ring-attention hook: causal masking on GLOBAL positions via the
    dynamic q_offset/k_offset SMEM scalars, compiled on chip."""
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import _flash_fwd_pallas

    rng = np.random.RandomState(10)
    B, T, H, D = 1, 128, 2, 64
    q, k, v = (jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
               for _ in range(3))

    with jax.default_device(_tpu_dev()):
        # q rows at global positions 128..255, k at 0..127 -> fully visible
        out_past, _ = jax.jit(lambda q, k, v: _flash_fwd_pallas(
            q, k, v, None, sm_scale=D ** -0.5, causal=True,
            block_q=128, block_k=128, q_offset=128, k_offset=0))(q, k, v)
        # diagonal shard: plain causal
        out_diag, _ = jax.jit(lambda q, k, v: _flash_fwd_pallas(
            q, k, v, None, sm_scale=D ** -0.5, causal=True,
            block_q=128, block_k=128, q_offset=0, k_offset=0))(q, k, v)
        # future shard: fully masked -> zeros, lse = NEG_INF
        out_fut, lse_fut = jax.jit(lambda q, k, v: _flash_fwd_pallas(
            q, k, v, None, sm_scale=D ** -0.5, causal=True,
            block_q=128, block_k=128, q_offset=0, k_offset=128))(q, k, v)

    qs, ks, vs = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    ref_past = dot_product_attention(qs, ks, vs).transpose(0, 2, 1, 3)
    ref_diag = dot_product_attention(qs, ks, vs,
                                     causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out_past), np.asarray(ref_past),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(out_diag), np.asarray(ref_diag),
                               atol=2e-4, rtol=2e-4)
    assert np.allclose(np.asarray(out_fut), 0.0)
    assert np.all(np.asarray(lse_fut) <= -1e29)


def test_flash_attention_sublane_only_shape_on_chip():
    """T=136 (17x8, not a 128-multiple): whole-array blocks equal to the
    array dims — the Mosaic edge _pick_block's sublane rule permits."""
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(11)
    B, T, H, D = 1, 136, 1, 32
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    with jax.default_device(_tpu_dev()):
        # explicit blocks force the KERNEL (the r5 shape dispatch would
        # otherwise route this sub-crossover shape to the jnp path);
        # whole-array 136-blocks still exercise the sublane rule.
        out = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=136, block_k=136))(q, k, v)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_ring_flash_kernel_under_default_vma_on_chip():
    """The ring-flash Mosaic kernel path must trace and run under
    shard_map's DEFAULT vma tracking (VERDICT r2 next #4): the kernels
    pcast-align their rank-varying offset operands (pallas_compat.
    align_vma), so no check_vma=False escape hatch is needed.  The jnp
    fallback is monkeypatched to fail loudly, proving the kernel ran."""
    import sys

    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from apex_tpu.ops.attention import blockwise_attention

    import apex_tpu.parallel.ring_attention  # noqa: F401  (registers module)
    ra = sys.modules["apex_tpu.parallel.ring_attention"]

    rng = np.random.RandomState(3)
    B, T, H, D = 2, 1024, 4, 64
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16)
               for _ in range(3))

    def _no_fallback(*a, **k):
        raise AssertionError("ring_flash fell back to the jnp ring under "
                             "default vma tracking")

    orig = ra.ring_attention
    ra.ring_attention = _no_fallback
    try:
        mesh = Mesh(np.array(jax.devices("tpu")[:1]), ("sp",))
        f = shard_map(
            lambda q, k, v: ra.ring_flash_attention(q, k, v, "sp",
                                                    causal=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"))          # default check_vma=True
        out = jax.jit(f)(q, k, v)
        ref = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=8e-3, rtol=8e-3)

        g = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(f(a, b, c).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(
                blockwise_attention(a, b, c,
                                    causal=True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=0.1, rtol=0.1)
    finally:
        ra.ring_attention = orig


def test_flash_2d_bias_kernels_on_chip():
    """Mosaic: [B,T,S] head-broadcast bias fwd + grads vs oracle — incl.
    the db2 kernel's head-innermost resident accumulation, which interpret
    mode cannot validate (revisited output blocks only stay resident on
    real Pallas TPU grids)."""
    from apex_tpu.ops.attention import blockwise_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    B, T, H, D = 2, 512, 4, 64
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D) * .5, jnp.bfloat16)
               for _ in range(3))
    seg = jnp.asarray(rng.randint(0, 3, (B, T)))
    bias = jnp.where(seg[:, :, None] == seg[:, None, :], 0.0,
                     -1e30).astype(jnp.float32)

    for causal in (False, True):
        f = lambda q, k, v, bias: flash_attention(
            q, k, v, causal=causal, bias=bias, block_q=128, block_k=128)
        ref = lambda q, k, v, bias: blockwise_attention(
            q, k, v, causal=causal, bias=bias[:, None])
        with jax.default_device(_tpu_dev()):
            out = jax.jit(f)(q, k, v, bias)
            g = jax.jit(jax.grad(
                lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2, 3)))(q, k, v, bias)
        r = ref(q, k, v, bias)
        gr = jax.jit(jax.grad(
            lambda *a: jnp.sum(ref(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3)))(q, k, v, bias)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=5e-3, rtol=5e-3)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=0.08, rtol=0.08)


def test_tp_self_attention_flash_kernel_on_chip():
    """dp x tp style head-parallel attention on a 1-device tp mesh under
    DEFAULT shard_map: the DEFAULT attention_fn must run the Mosaic flash
    kernel (jnp fallback forbidden) and match the dense reference.  T is
    above the r5 shape-dispatch crossover so the default path really is
    the kernel path here; the dispatch itself (sub-crossover shapes
    routing to jnp) is covered by test_flash_dispatch_* in
    tests/test_flash_attention.py."""
    # NOTE: `import apex_tpu.ops.flash_attention as fa` binds the
    # FUNCTION re-exported by ops/__init__ (it shadows the submodule
    # attribute) — import the symbol directly instead.
    from apex_tpu.ops.flash_attention import _KERNEL_MIN_KV
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.parallel.tensor_parallel import tp_self_attention

    rng = np.random.RandomState(5)
    B, T, d, H, hd = 2, max(1024, _KERNEL_MIN_KV), 64, 4, 32
    x = jnp.asarray(rng.randn(B, T, d) * .5, jnp.float32)
    wqkv = jnp.asarray(rng.randn(d, 3, H, hd) * .2, jnp.float32)
    wo = jnp.asarray(rng.randn(H * hd, d) * .2, jnp.float32)

    import apex_tpu.ops.attention as att
    orig = att.blockwise_attention

    def _no_fallback(*a, **k):
        raise AssertionError("tp flash attention fell back to jnp")

    att.blockwise_attention = _no_fallback
    try:
        mesh = Mesh(np.array(jax.devices("tpu")[:1]), ("tp",))
        f = shard_map(
            lambda x, wq, wo: tp_self_attention(x, wq, wo, H, "tp",
                                                causal=True),
            mesh=mesh, in_specs=(P(), P(None, None, "tp"), P("tp")),
            out_specs=P())
        out = jax.jit(f)(x, wqkv, wo)
    finally:
        att.blockwise_attention = orig

    qkv = jnp.einsum("btd,dche->btche", x, wqkv)
    ctx = dot_product_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                                causal=True)
    ref = ctx.reshape(B, T, -1) @ wo
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)


def test_flash_gqa_kernels_on_chip():
    """Mosaic GQA: the 5-D backward grid's resident dk/dv accumulation
    across the group-member dim is TPU-specific — interpret mode cannot
    validate it.  With key-padding bias so the db accumulation is
    exercised under grouping too."""
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    B, T, H, HKV, D = 2, 512, 8, 2, 64
    q = jnp.asarray(rng.randn(B, T, H, D) * .5, jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, T, HKV, D) * .5, jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, T, HKV, D) * .5, jnp.bfloat16)
    kb = jnp.where(jnp.arange(T)[None, :] < 400, 0.0,
                   -1e9) * jnp.ones((B, 1))

    def ref(q, k, v, causal):
        kr = jnp.repeat(k, H // HKV, axis=2)
        vr = jnp.repeat(v, H // HKV, axis=2)
        return dot_product_attention(q, kr, vr, causal=causal,
                                     bias=kb[:, None, None, :])

    for causal in (False, True):
        f = lambda q, k, v: flash_attention(
            q, k, v, causal=causal, key_padding_bias=kb,
            block_q=128, block_k=128)
        with jax.default_device(_tpu_dev()):
            out = jax.jit(f)(q, k, v)
            g = jax.jit(jax.grad(
                lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2)))(q, k, v)
        r = ref(q, k, v, causal)
        gr = jax.jit(jax.grad(
            lambda *a: jnp.sum(ref(*a, causal).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=1e-2, rtol=1e-2)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=0.2, rtol=0.1)


@pytest.mark.parametrize("chunk_keys", [None, 512])
def test_flash_sliding_window_on_chip(chunk_keys, monkeypatch):
    """Mosaic: bounded sliding-window grid (virtual-negative KV blocks
    clamped in the index maps, dead steps predicated off) vs the band-bias
    oracle — fwd + grads.  ``chunk_keys``: the backward's resident-VMEM
    budget shrunk to so many keys, so that the band crosses four KV
    chunks (span 3 < a chunk's 4 blocks < 16 Q blocks) and each call
    skips the tiles of it that lie outside its keys."""
    import importlib
    from apex_tpu.ops.attention import dot_product_attention
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    NEG_INF, flash_attention = fa.NEG_INF, fa.flash_attention

    rng = np.random.RandomState(0)
    B, T, H, D, W = 1, 2048, 4, 64, 256
    if chunk_keys:
        monkeypatch.setattr(fa, "_bwd_kv_chunk",
                            lambda tk, *_: (chunk_keys, 16 * 2**20))
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D) * .5, jnp.bfloat16)
               for _ in range(3))
    band = jnp.where(
        (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]) < W, 0.0, NEG_INF)

    f = lambda q, k, v: flash_attention(q, k, v, causal=True, window=W,
                                        block_q=128, block_k=128)
    ref = lambda q, k, v: dot_product_attention(q, k, v, causal=True,
                                                bias=band[None, None])
    with jax.default_device(_tpu_dev()):
        out = jax.jit(f)(q, k, v)
        g = jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
    r = ref(q, k, v)
    gr = jax.jit(jax.grad(
        lambda *a: jnp.sum(ref(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32),
                               atol=1e-2, rtol=1e-2)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=0.1, rtol=0.1)


def test_layer_norm_dispatch_structural():
    """The r5 auto dispatch is visible in the lowering: below the
    in-context crossover the jitted program contains NO layer-norm
    custom call (pure XLA fusion); at/above it, exactly the kernel.
    Lowering only — no compile, so this stays cheap on chip."""
    from apex_tpu.normalization.fused_layer_norm import fused_layer_norm

    with jax.default_device(_tpu_dev()):
        f = jax.jit(lambda x: fused_layer_norm(x, 768))
        small = f.lower(
            jax.ShapeDtypeStruct((2048, 768), jnp.bfloat16)).as_text()
        assert "tpu_custom_call" not in small
        big = f.lower(
            jax.ShapeDtypeStruct((8192, 768), jnp.bfloat16)).as_text()
        assert "tpu_custom_call" in big


# -- kernels added after the last chip run: registry case vs jnp reference ----
#
# ``check_against_reference`` compiles the spec's tune case with Mosaic
# (forward AND backward through the custom VJP), asserts the kernel — not
# the jnp path — is what was traced, and compares with the module's jnp
# reference within the spec's tolerance.

def test_quantized_matmul_on_chip():
    from apex_tpu.tune import registry
    from apex_tpu.tune.measure import check_against_reference

    spec = registry.get_spec("quantized_matmul")
    check_against_reference(spec, spec.example_shape)
    # a projection-sized shape with a ragged row count
    check_against_reference(spec, {"m": 2000, "k": 768, "n": 3072,
                                   "dtype": "bfloat16"})
