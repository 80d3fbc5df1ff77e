"""Granite-4.0-H style hybrid decoder: Mamba-2 (SSD) layers beside GQA
attention layers, by a list (flax, TPU-first).

The architecture of ``ibm-granite/granite-4.0-h-micro`` (``model_type``
``granitemoehybrid`` with no routed experts), which the defaults below
spell out.  With ``m_e``, ``m_r``, ``m_a``, ``m_l`` the embedding, residual,
attention and logit multipliers::

    h = wte[ids] * m_e
    per layer:  h = h + m_r * mixer(RMSNorm(h))          # by layer_types[l]
                h = h + m_r * W_out(silu(g) * u),  [g, u] = W_in RMSNorm(h)
    logits = (RMSNorm(h) @ wte^T) / m_l                   # tied, float32

Mamba-2 mixer: ``[z | xBC | dt] = in_proj(x)``; ``xBC = silu(causal
depthwise conv(xBC) + b)``; ``[x | B | C] = split(xBC)``; ``dt = softplus(dt
+ dt_bias)``; ``A = -exp(A_log)``; ``y = ssd(x, dt, A, B, C, D)``
(:mod:`apex_tpu.ops.ssd`); ``y = RMSNorm(y * silu(z))`` over all of
``d_inner`` (over each group's channels where ``n_groups > 1``);
``out_proj(y)``.  Between its two projections the mixer keeps
every array as ``[batch, chunk, channels, tokens of the chunk]``, which is
what the scan's products read and write: the conv reads a chunk's first
tokens from the chunk before, nothing is re-tiled, and what crosses HBM is
in the compute dtype (float32 between a load and a store; ``PERF.md``, PR
28).  The norm's factor of a token is applied to ``out_proj``'s float32 sums,
where it commutes.  Attention: q, k, v, o without bias, causal, no
positional encoding, scores scaled by ``m_a`` through ``ops.flash_attention``
and its shape dispatch.

Every layer is a ``jax.checkpoint`` that saves its input only: at the
published widths a training step does not fit one chip otherwise.  bf16
matmuls with float32 norms, decays, softmax statistics and loss.

Named scopes (metadata, like ``training.PHASE_SCOPES``): ``apex.ssm``
around the whole mixer, and inside it ``apex.ssm.conv``, ``apex.ssm.scan``
and ``apex.ssm.norm``; the backward rules of the conv and of the scan's
products inherit the scope they were called under.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..amp.policy import default_norm_predicate
from ..normalization import RMSNorm, gated_rms_norm_factors
from ..ops.flash_attention import flash_attention
from ..ops.ssd import causal_conv_silu, ssd_chunked

#: the scopes of the Mamba-2 mixer, outermost first
SSM_SCOPES = ("apex.ssm", "apex.ssm.conv", "apex.ssm.scan", "apex.ssm.norm")
(_SSM, _SSM_CONV, _SSM_SCAN, _SSM_NORM) = SSM_SCOPES

#: one period of the published list of layer kinds (positions 5, 15, 25, 35
#: of 40 are attention)
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

_dense_init = nn.initializers.normal(0.02)


def keep_fp32(path: str) -> bool:
    """``make_train_step(norm_predicate=keep_fp32)``: what amp O2 leaves in
    float32 in this model: the norm weights, and the mixer's per-head ``A_log``,
    ``dt_bias`` and ``D`` (a bf16 ``A_log`` would move every decay by up to
    1.5%).  The mixer refuses them in any other dtype, so a caller that
    forgets this fails at the first trace."""
    return (default_norm_predicate(path)
            or path.rsplit("/", 1)[-1] in ("A_log", "dt_bias", "D"))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1/sqrt(taps), the depthwise ``nn.Conv1d`` default."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dense(features, dtype, name):
    return nn.DenseGeneral(features, axis=-1, use_bias=False, dtype=dtype,
                           kernel_init=_dense_init, name=name)


class _Leaf(nn.Module):
    """One float32 parameter under a sub-module's name.  The tree keeps
    ``nn.Dense``'s ``kernel`` and ``RMSNorm``'s ``scale`` where the references
    and the checkpoints read them (same names, shapes and initialisation),
    while the mixer writes the products around them itself."""
    leaf: str
    initializer: Callable
    shape: Sequence[int]

    @nn.compact
    def __call__(self):
        return self.param(self.leaf, self.initializer, tuple(self.shape),
                          jnp.float32)


def _grouped_gated_norm(y, z, scale, groups, eps):
    """``RMSNorm(y * silu(z))`` over each of ``groups`` runs of channels
    (axis 2 of ``[batch, chunk, channels, tokens]``), float32 between the
    load and the store."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = gated.reshape(gated.shape[:2] + (groups, -1) + gated.shape[3:])
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=3, keepdims=True) + eps)
    return (by_group.reshape(gated.shape) * scale[:, None]).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    """``n_groups`` groups of B and C, each read by ``num_heads / n_groups``
    heads; with more than one group the gated norm is over each group's
    ``num_heads * head_dim / n_groups`` channels (a tensor-parallel rank that
    holds whole groups norms locally), with one over all of them."""
    num_heads: int = 64
    head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_width: int = 4
    chunk_size: int = 256
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.n_groups)
        d_inner = h * p
        d_conv = d_inner + 2 * g * n
        b, t, d = x.shape
        q = min(self.chunk_size, t)
        tail = -t % q
        lead = (b, (t + tail) // q)
        with jax.named_scope(_SSM):
            # From here to out_proj every array is [batch, chunk, channels,
            # tokens of the chunk]: what the scan's products read and write,
            # so nothing in between is re-tiled.  Padded tokens come last
            # and the mixer is causal: they reach no output that is kept.
            if tail:
                x = jnp.pad(x, ((0, 0), (0, tail), (0, 0)))
            x = x.reshape(lead + (q, d)).astype(self.dtype)
            w_in = _Leaf("kernel", _dense_init, (d, d_inner + d_conv + h),
                         name="in_proj")().astype(self.dtype)
            z, xbc, dt = jnp.split(jnp.einsum("bcqd,de->bceq", x, w_in),
                                   [d_inner, d_inner + d_conv], axis=2)
            with jax.named_scope(_SSM_CONV):
                taps = self.param("conv_kernel", _conv_init,
                                  (self.conv_width, d_conv), jnp.float32)
                bias = self.param("conv_bias", nn.initializers.zeros,
                                  (d_conv,), jnp.float32)
                xbc = causal_conv_silu(xbc, taps.astype(jnp.float32),
                                       bias.astype(jnp.float32))
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=dtype)), (h,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
            for name, leaf in (("A_log", a_log), ("dt_bias", dt_bias),
                               ("D", skip)):
                if leaf.dtype != jnp.float32:
                    raise TypeError(
                        f"Mamba2Mixer: {name} arrived as {leaf.dtype}; the "
                        f"decays are float32 whatever the compute dtype.  An "
                        f"amp cast rounded it: pass norm_predicate="
                        f"models.granite_hybrid.keep_fp32 to make_train_step")
            with jax.named_scope(_SSM_SCAN):
                xs, b_, c_ = jnp.split(xbc, [d_inner, d_inner + g * n], axis=2)
                y = ssd_chunked(
                    xs.reshape(lead + (h, p, q)),
                    jax.nn.softplus(dt.astype(jnp.float32) + dt_bias[:, None]),
                    -jnp.exp(a_log),
                    b_.reshape(lead + (g, n, q)), c_.reshape(lead + (g, n, q)),
                    skip)
            with jax.named_scope(_SSM_NORM):
                scale = _Leaf("scale", nn.initializers.ones, (d_inner,),
                              name="norm")()
                y = y.reshape(lead + (d_inner, q))
                if g == 1:
                    y, inv_rms = gated_rms_norm_factors(y, z, scale, self.eps,
                                                        axis=2)
                else:
                    y = _grouped_gated_norm(y, z, scale, g, self.eps)
            w_out = _Leaf("kernel", _dense_init, (d_inner, d),
                          name="out_proj")().astype(self.dtype)
            if g > 1:       # a factor a group does not commute with out_proj
                out = jnp.einsum("bceq,ed->bcqd", y, w_out)
                return out.astype(self.dtype).reshape(b, t + tail, d)[:, :t]
            # the norm's factor of a token commutes with the product over the
            # channels: it scales the float32 sums, one pass over y earlier
            out = jnp.einsum("bceq,ed->bcqd", y, w_out,
                             preferred_element_type=jnp.float32)
            out = (out * jnp.swapaxes(inv_rms, 2, 3)).astype(self.dtype)
            return out.reshape(b, t + tail, d)[:, :t]


class GQAttention(nn.Module):
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    sm_scale: float = 0.015625
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        proj = lambda name, heads: nn.DenseGeneral(
            (heads, self.head_dim), use_bias=False, dtype=self.dtype,
            kernel_init=_dense_init, name=name)(x)
        ctx = flash_attention(proj("query", self.num_heads),
                              proj("key", self.num_kv_heads),
                              proj("value", self.num_kv_heads),
                              causal=True, sm_scale=self.sm_scale)
        return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, kernel_init=_dense_init,
                               name="out")(ctx.astype(self.dtype))


class HybridLayer(nn.Module):
    """One layer: the mixer of its kind, then the shared SwiGLU MLP, each
    behind an RMSNorm and scaled into the residual stream."""
    kind: str
    mixer: Any                      # the mixer's constructor arguments
    mlp_dim: int = 8192
    residual_multiplier: float = 0.22
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        if self.kind == "mamba":
            mixer = Mamba2Mixer(**self.mixer, eps=self.eps, dtype=self.dtype,
                                name="mamba")
        elif self.kind == "attention":
            mixer = GQAttention(**self.mixer, dtype=self.dtype,
                                name="attention")
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        m = mixer(RMSNorm(self.eps, name="norm1")(h))
        h = h + (self.residual_multiplier * m).astype(h.dtype)
        gate, up = jnp.split(_dense(2 * self.mlp_dim, self.dtype, "mlp_in")(
            RMSNorm(self.eps, name="norm2")(h)), 2, axis=-1)
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        m = _dense(d, self.dtype, "mlp_out")(act.astype(gate.dtype))
        return h + (self.residual_multiplier * m).astype(h.dtype)


def head_logits(h, w, divide_by=None):
    """Float32 logits ``h @ w^T`` (over ``divide_by``) ``[B, T, V]``, computed
    as one product over the ``B * T`` flattened tokens.  The fused loss
    takes ``logits.reshape(-1, V)`` and hands back ``g * r`` in that shape
    (``contrib.xentropy``); with the product written over ``[B, T]`` a
    reshape stands between that multiplication and the head's two gradient
    products, and XLA's TPU fusions take no producer through a reshape: it
    writes ``g * r`` out.  Over flattened tokens the two reshapes cancel
    and the multiplication rides in both products' prologues."""
    b, t, d = h.shape
    logits = jnp.einsum("nd,vd->nv", h.reshape(b * t, d), w.astype(h.dtype),
                        preferred_element_type=jnp.float32)
    if divide_by is not None:
        logits = logits / divide_by
    return logits.reshape(b, t, -1)


class GraniteHybrid(nn.Module):
    """``__call__(input_ids) -> logits [B, T, V]`` (float32, tied head).
    The defaults are granite-4.0-h-micro's published ``config.json``."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    layer_types: Sequence[str] = PERIOD * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 8192
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1
    mamba_conv: int = 4
    mamba_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, input_ids):
        wte = self.param("wte", _dense_init,
                         (self.vocab_size, self.hidden_size), jnp.float32)
        mixers = {
            "mamba": dict(num_heads=self.mamba_heads,
                          head_dim=self.mamba_head_dim,
                          state_size=self.mamba_state,
                          n_groups=self.mamba_groups,
                          conv_width=self.mamba_conv,
                          chunk_size=self.mamba_chunk),
            "attention": dict(num_heads=self.num_heads,
                              num_kv_heads=self.num_kv_heads,
                              head_dim=self.hidden_size // self.num_heads,
                              sm_scale=self.attention_multiplier)}
        h = (wte[input_ids] * self.embedding_multiplier).astype(self.dtype)
        layer = nn.remat(HybridLayer)       # saves the layer's input only
        for i, kind in enumerate(self.layer_types):
            h = layer(kind, mixers.get(kind), self.mlp_dim,
                      self.residual_multiplier, self.eps, self.dtype,
                      name=f"layer_{i}")(h)
        h = RMSNorm(self.eps, name="norm_f")(h)
        return head_logits(h, wte, self.logits_scaling)


def granite_hybrid_tiny(**kw):
    """The same block at toy widths: one period of ten layers by default."""
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("layer_types", PERIOD)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("mlp_dim", 128)
    kw.setdefault("mamba_heads", 8)
    kw.setdefault("mamba_head_dim", 16)
    kw.setdefault("mamba_state", 16)
    kw.setdefault("mamba_chunk", 16)
    return GraniteHybrid(**kw)
