"""Flash-attention kernel parity vs the jnp oracle.

The CPU tests run the Pallas kernels in interpreter mode (same kernel
code path as on chip, minus Mosaic lowering); the ``tpu``-marked
counterparts in ``test_pallas_tpu.py`` execute the compiled kernels.
Mirrors the fallback-vs-kernel strategy of the reference's L0 kernel
tests (``tests/L0/run_fused_layer_norm``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.ops.attention import dot_product_attention
from apex_tpu.ops.flash_attention import _pick_block, flash_attention


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_interpret_matches_oracle(causal):
    B, T, H, D = 2, 256, 4, 64
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_interpret_key_padding_bias():
    B, T, H, D = 2, 256, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    valid = jnp.arange(T)[None, :] < jnp.array([200, 64])[:, None]
    kb = jnp.where(valid, 0.0, -1e9)
    out = flash_attention(q, k, v, key_padding_bias=kb, block_q=128,
                          block_k=128, interpret=True)
    ref = dot_product_attention(q, k, v, bias=kb[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_interpret_grads_match_oracle(causal):
    B, T, H, D = 1, 256, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    flash = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True))
    ref = loss(lambda q, k, v: dot_product_attention(q, k, v, causal=causal))
    g1 = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_interpret_grads_with_bias():
    B, T, H, D = 1, 128, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    valid = jnp.arange(T)[None, :] < 100
    kb = jnp.where(valid, 0.0, -1e9) * jnp.ones((B, 1))

    # Soft (finite) bias so the bias gradient is non-trivially nonzero.
    kb_soft = jnp.asarray(np.random.RandomState(9).randn(B, T), jnp.float32)

    def f_flash(q, k, v, bias):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, key_padding_bias=bias, block_q=128, block_k=128,
            interpret=True)))

    def f_ref(q, k, v, bias):
        return jnp.sum(jnp.sin(dot_product_attention(
            q, k, v, bias=bias[:, None, None, :])))

    for bias in (kb, kb_soft):
        g1 = jax.grad(f_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        assert float(jnp.linalg.norm(g2[3])) > 0 or bias is kb
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)


def test_flash_fallback_off_tpu_non_tiling_seq():
    # T=100 is not sublane-aligned → jnp blockwise fallback on ANY backend
    # (_pick_block returns None), asserted numerically here.
    B, T, H, D = 2, 100, 2, 16
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    out = flash_attention(q, k, v, causal=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_pick_block():
    assert _pick_block(128, 512) == 128      # t <= preferred, aligned → t
    assert _pick_block(104, 512) == 104      # sublane-aligned whole-array
    assert _pick_block(100, 512) is None     # unaligned → jnp fallback
    assert _pick_block(1024, 512) == 512     # divides
    assert _pick_block(768, 512) == 384      # largest 128-multiple divisor
    assert _pick_block(640, 512) == 128
    assert _pick_block(1000, 512) is None    # no 128-multiple divides


@pytest.mark.slow
def test_bert_flash_impl_matches_full_off_tpu():
    """attention_impl='flash' (fallback path off-TPU) == 'full' oracle."""
    from apex_tpu.models import bert_tiny

    ids = jnp.asarray(np.random.RandomState(0).randint(0, 1024, (2, 64)))
    m_full = bert_tiny(num_classes=None)
    m_flash = bert_tiny(num_classes=None, attention_impl="flash")
    params = m_full.init(jax.random.PRNGKey(0), ids)
    out_full = m_full.apply(params, ids)
    out_flash = m_flash.apply(params, ids)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out_flash),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape,blocks", [
    ((1, 136, 1, 32), (136, 136)),    # sublane-only alignment (17*8), 1 head
    pytest.param((3, 384, 5, 64), (128, 256),   # mismatched bq/bk, odd
                 marks=pytest.mark.slow),        # head count (slowest case)
    ((2, 256, 2, 128), (256, 128)),   # wide head_dim
    ((1, 512, 3, 16), (512, 128)),    # narrow head_dim, whole-seq q block
])
def test_flash_interpret_fuzz_shapes(shape, blocks):
    """Chunk-boundary style fuzzing (the reference's multi-tensor fuzz
    strategy applied to the attention kernel): odd head counts, sublane-
    only sequence alignment, asymmetric block sizes, extreme head dims —
    fwd AND grads vs the oracle."""
    B, T, H, D = shape
    bq, bk = blocks
    q, k, v = (_rand(shape, s + 10) for s in range(3))

    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.cos(fn(q, k, v)))

    g1 = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)


def test_flash_interpret_inf_inputs_propagate():
    """Non-finite Q rows must surface as non-finite outputs (the amp
    overflow machinery depends on inf/nan propagating, reference
    multi-tensor inf/NaN-injection strategy)."""
    B, T, H, D = 1, 128, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    q = q.at[0, 5, 0, :].set(np.inf)
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    assert not np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_flash_interpret_2d_bias_fwd_and_grads(causal):
    """[B, T, S] head-broadcast additive bias (segment masks, relative
    position biases) on the kernel path — fwd + all four grads vs the
    oracle, incl. the head-summed dbias from the dedicated kernel."""
    B, T, H, D = 2, 256, 3, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    rng = np.random.RandomState(7)
    seg = jnp.asarray(rng.randint(0, 2, (B, T)))
    hard = jnp.where(seg[:, :, None] == seg[:, None, :], 0.0,
                     -1e30).astype(jnp.float32)
    soft = jnp.asarray(rng.randn(B, T, T), jnp.float32)

    def f_flash(q, k, v, bias):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, bias=bias, block_q=128, block_k=128,
            interpret=True)))

    def f_ref(q, k, v, bias):
        return jnp.sum(jnp.sin(dot_product_attention(
            q, k, v, causal=causal, bias=bias[:, None])))

    for bias in (hard, soft):
        np.testing.assert_allclose(
            float(f_flash(q, k, v, bias)), float(f_ref(q, k, v, bias)),
            atol=1e-4, rtol=1e-4)
        g1 = jax.grad(f_flash, argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)
        if bias is soft:
            assert float(jnp.linalg.norm(g2[3])) > 0
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4)


def test_flash_2d_bias_combines_with_key_padding():
    """bias= and key_padding_bias= together fold into one additive term."""
    B, T, H, D = 1, 128, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    rng = np.random.RandomState(3)
    b2 = jnp.asarray(rng.randn(B, T, T), jnp.float32)
    kb = jnp.asarray(rng.randn(B, T), jnp.float32)

    out = flash_attention(q, k, v, bias=b2, key_padding_bias=kb,
                          block_q=128, block_k=128, interpret=True)
    ref = dot_product_attention(
        q, k, v, bias=(b2 + kb[:, None, :])[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_per_head_bias_falls_back_to_jnp():
    """[B, H, T, S] per-head bias: no kernel support, documented jnp
    fallback computes the same function; 5-D shapes are rejected."""
    B, T, H, D = 1, 128, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    b4 = jnp.asarray(np.random.RandomState(1).randn(B, H, T, T) * .3,
                     jnp.float32)
    out = flash_attention(q, k, v, bias=b4, interpret=True)
    ref = dot_product_attention(q, k, v, bias=b4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="bias must be"):
        flash_attention(q, k, v, bias=jnp.zeros((B, H, T, T, 1)),
                        interpret=True)


def test_flash_broadcastable_3d_bias():
    """[B,1,S] broadcastable bias is materialized for the kernel path and
    its gradient folds back to the caller's shape; incompatible shapes
    raise loudly instead of reading clamped garbage."""
    B, T, H, D = 1, 256, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    nb = jnp.asarray(np.random.RandomState(2).randn(B, 1, T), jnp.float32)

    out = flash_attention(q, k, v, bias=nb, block_q=128, block_k=128,
                          interpret=True)
    ref = dot_product_attention(q, k, v, bias=nb[:, None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda b: jnp.sum(flash_attention(
        q, k, v, bias=b, block_q=128, block_k=128, interpret=True) ** 2))(nb)
    gr = jax.grad(lambda b: jnp.sum(dot_product_attention(
        q, k, v, bias=b[:, None]) ** 2))(nb)
    assert g.shape == nb.shape
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               atol=5e-4, rtol=5e-4)

    with pytest.raises(ValueError, match="not broadcastable"):
        flash_attention(q, k, v, bias=jnp.zeros((B, 3, T)), interpret=True)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_kv", [1, 2])
def test_flash_gqa_interpret_matches_repeat_oracle(causal, n_kv):
    """Grouped-query / multi-query attention: kv heads shared across
    query heads through the kernel index maps must equal the repeat-KV
    oracle, fwd + grads (dk/dv come back at kv-head shape, the group-sum
    of the repeated oracle's grads)."""
    B, T, H, D = 2, 256, 4, 32
    q = _rand((B, T, H, D), 0)
    k = _rand((B, T, n_kv, D), 1)
    v = _rand((B, T, n_kv, D), 2)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128, interpret=True)

    def ref(q, k, v):
        kr = jnp.repeat(k, H // n_kv, axis=2)
        vr = jnp.repeat(v, H // n_kv, axis=2)
        return dot_product_attention(q, kr, vr, causal=causal)

    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), argnums=(0, 1, 2))(
        q, k, v)
    assert g1[1].shape == k.shape and g1[2].shape == v.shape
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_gqa_rejects_nondivisible_heads():
    q = _rand((1, 128, 4, 16), 0)
    kv = _rand((1, 128, 3, 16), 1)
    with pytest.raises(ValueError, match="kv heads"):
        flash_attention(q, kv, kv, interpret=True)


@pytest.mark.slow
def test_gpt_gqa_forward_and_train():
    """GPT with num_kv_heads (llama-style GQA) trains end-to-end off-TPU
    (flash fallback repeats KV); kv projections carry fewer heads."""
    from apex_tpu.models import gpt_tiny

    model = gpt_tiny(num_kv_heads=2)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 1024, (2, 64)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    kshape = params["block_0"]["attention"]["key"]["kernel"].shape
    qshape = params["block_0"]["attention"]["query"]["kernel"].shape
    assert kshape[1] == 2 and qshape[1] == 4
    out = model.apply({"params": params}, ids)
    assert out.shape == (2, 64, 1024) and np.isfinite(np.asarray(out)).all()

    # one real amp-O2 train step: grads flow through the kv-head-shaped
    # projections and the repeated-KV fallback, loss decreases over steps
    from apex_tpu import training
    from apex_tpu.training import make_train_step

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch)
        logp = jax.nn.log_softmax(logits[:, :-1])
        tgt = batch[:, 1:]
        return -jnp.mean(jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    init_fn, step_fn = make_train_step(loss_fn, training.adam(1e-3),
                                       opt_level="O2")
    state = init_fn(params)
    step = jax.jit(step_fn, donate_argnums=(0,))
    losses = []
    for _ in range(8):
        state, m = step(state, ids)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("window", [1, 100, 150, 256, 511])
def test_flash_sliding_window_matches_band_oracle(window):
    """Sliding-window local attention (bounded kernel grid: only
    ceil(w/bk)+1 KV blocks per Q block are visited) vs the full-attention
    oracle with an explicit band bias — fwd + grads."""
    from apex_tpu.ops.flash_attention import NEG_INF

    B, T, H, D = 1, 512, 2, 32
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    band = jnp.where(
        (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]) < window,
        0.0, NEG_INF)

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=128, block_k=128, interpret=True)

    def ref(q, k, v):
        return dot_product_attention(q, k, v, causal=True,
                                     bias=band[None, None])

    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_window_with_bias_and_mqa():
    """window + learnable [B,T,S] bias + MQA compose; dbias is zero
    outside the band (the db2 pass keeps the full grid so out-of-band
    blocks are written, not left undefined)."""
    from apex_tpu.ops.flash_attention import NEG_INF

    # T=512, W=100 (span 2 < nk 4): the BOUNDED grid runs, covering the
    # clamped bias index maps under virtual-negative ki.
    B, T, H, D, W = 1, 512, 2, 32, 100
    q = _rand((B, T, H, D), 0)
    k1 = _rand((B, T, 1, D), 1)
    v1 = _rand((B, T, 1, D), 2)
    bias = _rand((B, T, T), 3) * 0.3
    band = jnp.where(
        (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]) < W, 0.0, NEG_INF)

    def f(q, k, v, bi):
        return flash_attention(q, k, v, causal=True, window=W, bias=bi,
                               block_q=128, block_k=128, interpret=True)

    def ref(q, k, v, bi):
        return dot_product_attention(
            q, jnp.repeat(k, H, 2), jnp.repeat(v, H, 2), causal=True,
            bias=bi[:, None] + band[None, None])

    g1 = jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2, 3))(
        q, k1, v1, bias)
    g2 = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2, 3))(
        q, k1, v1, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)
    assert np.isfinite(np.asarray(g1[3])).all()
    # out-of-band bias grad is exactly zero
    oob = np.asarray(g1[3])[0][np.asarray(band) < -1e29]
    assert np.all(oob == 0.0)


def test_flash_window_requires_causal():
    q, k, v = (_rand((1, 128, 2, 32), s) for s in range(3))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=64, interpret=True)


def test_flash_dispatch_predicate():
    """r5 shape dispatch: defaults below the crossover route to jnp; an
    explicit block size (even equal to the default values) forces the
    kernel; at/above the crossover defaults keep the kernel."""
    from apex_tpu.ops.flash_attention import (_KERNEL_MIN_KV,
                                              _dispatch_to_jnp)
    small = _KERNEL_MIN_KV // 2
    assert _dispatch_to_jnp(small, small, True)
    assert not _dispatch_to_jnp(small, small, False)   # explicit blocks
    assert not _dispatch_to_jnp(_KERNEL_MIN_KV, _KERNEL_MIN_KV, True)
    # mixed: a long KV with short Q (decode-ish chunk) keeps the kernel
    assert not _dispatch_to_jnp(small, _KERNEL_MIN_KV, True)


def test_flash_dispatch_routes_to_jnp_numerics():
    """The dispatched (jnp) path computes the same function: defaults at a
    sub-crossover shape vs explicit-block kernel in interpret mode."""
    q, k, v = (_rand((2, 128, 2, 32), s) for s in range(3))
    # defaults: sub-crossover -> jnp path (off-TPU it is the fallback
    # anyway; the assert is on VALUES, which must agree either way)
    out_default = flash_attention(q, k, v, causal=True)
    out_kernel = flash_attention(q, k, v, causal=True,
                                 block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out_default),
                               np.asarray(out_kernel), atol=2e-2, rtol=2e-2)


# -- decode-shaped causal inputs (ISSUE 11 satellite) -------------------------

def _suffix_causal_ref(q, k, v, key_padding_bias=None):
    """Reference for decode-shaped causal attention: run the FULL causal
    oracle over the whole sequence (queries = the last tq positions) and
    slice the suffix rows — token-for-token what a KV-cache decode must
    reproduce."""
    tq, tk = q.shape[1], k.shape[1]
    # embed the queries at their true (suffix) positions: pad with the
    # keys' own projections so positions 0..tk-tq-1 exist, then slice.
    bias = None
    if key_padding_bias is not None:
        bias = key_padding_bias[:, None, None, :]
    qi = (tk - tq) + jnp.arange(tq)[:, None]
    ki = jnp.arange(tk)[None, :]
    causal = jnp.where(qi >= ki, 0.0, -1e30)[None, None]
    bias = causal if bias is None else bias + causal
    return dot_product_attention(q, k, v, causal=False, bias=bias)


@pytest.mark.parametrize("tq", [1, 4, 7])
def test_decode_shaped_causal_matches_reference(tq):
    """causal with q_len < kv_len must suffix-align the queries (the
    KV-cache decode convention) — before the fix a q_len=1 causal call
    silently attended only key 0."""
    B, TK, H, D = 2, 96, 2, 16
    q = _rand((B, tq, H, D), 0)
    k = _rand((B, TK, H, D), 1)
    v = _rand((B, TK, H, D), 2)
    out = flash_attention(q, k, v, causal=True)
    ref = _suffix_causal_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_shaped_causal_with_live_mask():
    """One fresh token over a cache of TK slots with only the first L
    live (key_padding_bias masks the dead tail) — the serving engine's
    decode call shape."""
    B, TK, H, D = 3, 64, 2, 16
    live_len = jnp.array([5, 17, 64])
    q = _rand((B, 1, H, D), 3)
    k = _rand((B, TK, H, D), 4)
    v = _rand((B, TK, H, D), 5)
    kb = jnp.where(jnp.arange(TK)[None, :] < live_len[:, None], 0.0, -1e9)
    out = flash_attention(q, k, v, causal=True, key_padding_bias=kb)
    ref = _suffix_causal_ref(q, k, v, key_padding_bias=kb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_shaped_causal_kernel_path_matches():
    """A sublane-aligned short query block keeps the kernel path
    (interpret mode) — suffix alignment must hold there too, not only on
    the jnp fallback."""
    B, TQ, TK, H, D = 1, 8, 128, 2, 16
    q = _rand((B, TQ, H, D), 6)
    k = _rand((B, TK, H, D), 7)
    v = _rand((B, TK, H, D), 8)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=128,
                          interpret=True)
    ref = _suffix_causal_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_causal_more_queries_than_keys_raises():
    q = _rand((1, 8, 2, 16), 0)
    k = _rand((1, 4, 2, 16), 1)
    with pytest.raises(ValueError, match="q_len"):
        flash_attention(q, k, q * 0, causal=True)


def test_attention_sweep_tool_quick(tmp_path, monkeypatch, capsys):
    """``tools/attention_sweep.py``, the sweep the dispatch threshold
    cites, stands on its own (its execution-forcing fetch was imported
    from the deleted ``bench.py``): ``--quick`` on the CPU times all
    three implementations and writes its table."""
    import json
    import os
    import runpy
    import sys

    tool = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "attention_sweep.py")
    out = tmp_path / "sweep.json"
    monkeypatch.setattr(sys, "argv", [tool, "--quick", "--iters", "1",
                                      "--out", str(out)])
    runpy.run_path(tool, run_name="__main__")
    table = json.loads(out.read_text())
    (row,) = table["rows"]
    assert table["backend"] == "cpu"
    assert {"full_ms", "blockwise_ms", "flash_128_ms",
            "flash_best_ms", "jnp_best_ms"} <= set(row)
    assert all(row[k] > 0 for k in ("full_ms", "blockwise_ms",
                                    "flash_128_ms"))
    assert json.loads(capsys.readouterr().out.splitlines()[-1])[
        "n_rows"] == 1


# -- the fused backward kernel (ISSUE 30) --------------------------------------
# dq, dk and dv come from one `flash_bwd` pallas_call: dk/dv accumulate in
# whole-key-length scratch at row offsets ki * bk, so these cases force
# several Q and KV tiles (T 512, blocks 128) through it.

def _fa_module():
    import importlib
    return importlib.import_module("apex_tpu.ops.flash_attention")


def _grads_match_oracle(f, ref, args, atol=5e-4):
    argnums = tuple(range(len(args)))
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=argnums)(*args)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))),
                  argnums=argnums)(*args)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol, rtol=atol)
    return g1


def _tiled_case(tq, tk, n_kv, causal, with_bias, **kw):
    """(f, ref, args) over [1, T, 4, 32] inputs with 128-blocks: the kernel
    path in interpret mode against the materialized-scores oracle."""
    B, H, D = 1, 4, 32
    q = _rand((B, tq, H, D), 0)
    k = _rand((B, tk, n_kv, D), 1)
    v = _rand((B, tk, n_kv, D), 2)
    args = (q, k, v) + ((_rand((B, tk), 3),) if with_bias else ())

    def f(q, k, v, kb=None):
        return flash_attention(q, k, v, causal=causal, key_padding_bias=kb,
                               block_q=128, block_k=128, interpret=True,
                               **kw)

    def ref(q, k, v, kb=None):
        kr = jnp.repeat(k, H // n_kv, axis=2)
        vr = jnp.repeat(v, H // n_kv, axis=2)
        if causal:
            return _suffix_causal_ref(q, kr, vr, key_padding_bias=kb)
        bias = None if kb is None else kb[:, None, None, :]
        return dot_product_attention(q, kr, vr, bias=bias)

    return f, ref, args


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("n_kv", [4, 2, 1])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_many_tiles(causal, n_kv, with_bias):
    """4 x 4 tiles through the fused kernel, MHA / GQA 4:2 / MQA, with and
    without the key-padding bias: dq, dk, dv and the bias gradient against
    the oracle."""
    f, ref, args = _tiled_case(512, 512, n_kv, causal, with_bias)
    g = _grads_match_oracle(f, ref, args)
    if with_bias:
        assert float(jnp.linalg.norm(g[3])) > 0


@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("n_kv", [4, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_fewer_queries_than_keys(causal, n_kv,
                                                      with_bias):
    """q_len 256 < kv_len 512 (2 x 4 tiles; causal = suffix alignment, a
    static q_offset): the dk/dv rows no query tile reaches stay zero."""
    f, ref, args = _tiled_case(256, 512, n_kv, causal, with_bias)
    _grads_match_oracle(f, ref, args)


def _shrink_bwd_budget(monkeypatch, keys):
    """The fused backward keeps at most `keys` keys resident per call."""
    fa = _fa_module()
    monkeypatch.setattr(fa, "_bwd_kv_chunk",
                        lambda tk, *_: (min(tk, keys), 16 * 2**20))
    return fa


@pytest.mark.parametrize("vmem_mib,tk,d,itemsize,want", [
    (128, 1024, 64, 2, (1024, 16)),      # gpt2_small_o2.seq1024: the default
    (128, 4096, 64, 2, (4096, 20)),      # granite4_h_micro_o2.b2_seq4096
    (128, 16384, 128, 2, (16384, 44)),   # the most one call holds on a v5e
    (128, 32768, 64, 2, (16384, 44)),    # beyond it: two chunks
    (128, 32768, 64, 4, (10240, 42)),    # float32: 20 bytes a lane
    (64, 32768, 64, 2, (8192, 28)),      # half the VMEM, half the keys
    (16, 4096, 64, 2, (2048, 16)),       # never over the core's VMEM
])
def test_flash_backward_kv_chunk_follows_vmem(vmem_mib, tk, d, itemsize,
                                              want, monkeypatch):
    """Keys per `flash_bwd` call and its scoped-VMEM limit (MiB) from the
    core's VMEM: a quarter of it for the whole-length residents."""
    fa = _fa_module()
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: vmem_mib * 2**20)
    keys, limit = fa._bwd_kv_chunk(tk, d, itemsize, 1024)
    assert (keys, limit / 2**20) == want


def _count_pallas_calls(jaxpr):
    n, names = 0, []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            m, more = _count_pallas_calls(sub)
            n += m
            names += more
    return n, names


@pytest.mark.parametrize("case,want", [
    ("plain", 1), ("gqa", 1), ("key_bias", 1), ("window_bounded", 1),
    ("ring_offsets", 1), ("qk_bias", 2), ("keys_over_budget", 2),
    ("window_over_budget", 2)])
def test_flash_backward_pallas_call_count(case, want, monkeypatch):
    """The backward is ONE pallas_call named flash_bwd on every path (a
    [B,T,S] bias adds its own gradient kernel); keys beyond the VMEM
    budget take the same kernel once per KV chunk.  No flag chooses."""
    fa = _fa_module()
    B, H, T, D = 1, 4, 512, 32
    n_kv = 2 if case == "gqa" else H
    q = jnp.zeros((B, H, T, D))
    k = v = jnp.zeros((B, n_kv, T, D))
    lse = jnp.zeros((B, H, T, 1))
    kw = dict(sm_scale=0.2, causal=True, block_q=128, block_k=128,
              interpret=True)
    kb = jnp.zeros((B, T)) if case == "key_bias" else None
    if case == "qk_bias":
        kw["qk_bias"] = jnp.zeros((B, T, T))
    if case.startswith("window"):
        kw["window"] = 100
        assert fa._window_span(100, 128, 128, 0, 0, 4) == 2
    if case.endswith("over_budget"):
        _shrink_bwd_budget(monkeypatch, 256)

    def bwd(q, k, v, lse, do, qo, ko):
        if case != "ring_offsets":
            qo, ko = 0, 0
        return fa._flash_bwd_pallas(q, k, v, kb, q, lse, do, q_offset=qo,
                                    k_offset=ko, **kw)

    jaxpr = jax.make_jaxpr(bwd)(q, k, v, lse, q, jnp.int32(0), jnp.int32(0))
    n, names = _count_pallas_calls(jaxpr.jaxpr)
    assert n == want
    assert names.count("flash_bwd") == (1 if case == "qk_bias" else want)


@pytest.mark.parametrize("case", ["causal_gqa_bias", "window", "qk_bias",
                                  "noncausal"])
def test_flash_backward_kv_chunks_match_oracle(case, monkeypatch):
    """Keys beyond the resident-VMEM budget: the budget is shrunk to 256
    keys, so T 512 runs the fused kernel over two KV chunks (the second
    with a nonzero k_offset) and dq is summed across them."""
    B, T, H, D = 1, 512, 4, 32
    _shrink_bwd_budget(monkeypatch, 256)
    if case in ("causal_gqa_bias", "noncausal"):
        f, ref, args = _tiled_case(T, T, 2, case != "noncausal", True)
        _grads_match_oracle(f, ref, args)
        return
    q, k, v = (_rand((B, T, H, D), s) for s in range(3))
    if case == "window":          # span 3 of a chunk's 2: the masked grid
        _window_grads_match_oracle(q, k, v, 150)
    else:
        bias = _rand((B, T, T), 3) * 0.3
        f = lambda q, k, v, bi: flash_attention(
            q, k, v, causal=True, bias=bi, block_q=128, block_k=128,
            interpret=True)
        ref = lambda q, k, v, bi: dot_product_attention(
            q, k, v, causal=True, bias=bi[:, None])
        _grads_match_oracle(f, ref, (q, k, v, bias))


def _window_grads_match_oracle(q, k, v, window, n_kv=None, kb=None):
    fa = _fa_module()
    T, H = q.shape[1], q.shape[2]
    pos = jnp.arange(T)
    band = jnp.where((pos[:, None] - pos[None, :]) < window, 0.0,
                     fa.NEG_INF)[None, None]
    args = (q, k, v) + (() if kb is None else (kb,))

    def f(q, k, v, kb=None):
        return flash_attention(q, k, v, causal=True, window=window,
                               key_padding_bias=kb, block_q=128,
                               block_k=128, interpret=True)

    def ref(q, k, v, kb=None):
        bias = band if kb is None else band + kb[:, None, None, :]
        rep = H // k.shape[2]
        return dot_product_attention(q, jnp.repeat(k, rep, axis=2),
                                     jnp.repeat(v, rep, axis=2),
                                     causal=True, bias=bias)

    return _grads_match_oracle(f, ref, args)


@pytest.mark.parametrize("budget_keys,window,n_kv,with_bias", [
    (512, 150, 4, False),     # span 3 < a chunk's 4 blocks < nq 8
    (512, 150, 2, True),      # the same under GQA with the key bias
    (256, 300, 4, False),     # span 4 > a chunk's 2: a band over 3 chunks
    (384, 129, 1, True),      # 3-block chunks, the last one short (2)
])
def test_flash_backward_bounded_window_across_kv_chunks(
        budget_keys, window, n_kv, with_bias, monkeypatch):
    """The bounded sliding-window grid walks the band of the WHOLE key
    length (T 1,024 in 8 blocks); each KV chunk runs the tiles of it that
    it holds and skips the rest, those past its end too."""
    B, T, H, D = 1, 1024, 4, 32
    fa = _shrink_bwd_budget(monkeypatch, budget_keys)
    assert fa._window_span(window, 128, 128, 0, 0, 8) is not None
    q = _rand((B, T, H, D), 0)
    k, v = (_rand((B, T, n_kv, D), s) for s in (1, 2))
    kb = _rand((B, T), 3) if with_bias else None
    g = _window_grads_match_oracle(q, k, v, window, kb=kb)
    # the band reaches every chunk: no dk rows left at their zero fill
    assert float(jnp.abs(g[1][:, -128:]).max()) > 0
