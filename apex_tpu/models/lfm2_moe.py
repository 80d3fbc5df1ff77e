"""LFM2-MoE style decoder: gated short convolutions beside GQA attention with
rotary positions, a dense SwiGLU MLP in the leading layers and routed experts
after them, each by a list (flax, TPU-first).

The architecture of ``LiquidAI/LFM2-24B-A2B`` (``model_type`` ``lfm2_moe``),
which the defaults below spell out::

    h = wte[ids]
    per layer:  h = h + op_l(RMSNorm(h))         # by layer_types[l]
                h = h + ff_l(RMSNorm(h))         # dense MLP for l < num_dense_layers,
                                                 # else the routed experts
    logits = RMSNorm(h) @ wte^T                  # tied, float32

Short conv: ``[B | C | x] = in_proj(u)``; ``y_t = C_t * sum_k taps[k] (B
x)_{t - 2 + k}`` (:func:`apex_tpu.ops.gated_short_conv`: causal, depthwise, no
bias, no activation); ``out_proj(y)``.  Between its two projections the mixer
keeps tokens as the last axis, which is what the conv's shifted views read.
Attention: q, k, v, o without bias; ``q = rope(RMSNorm_head(q))``, ``k =
rope(RMSNorm_head(k))`` (:func:`apex_tpu.ops.qk_norm_rope`: one weight of
``head_dim`` for all query heads and one for all key heads, halves paired,
float32 angles); causal, scores scaled by ``head_dim ** -0.5``, through
``ops.flash_attention`` and its shape dispatch.  Dense MLP: ``W2(silu(W1 u) *
W3 u)``.  Routed experts: :func:`apex_tpu.ops.moe_layer`, sigmoid scores in
float32, top-k of ``scores + bias``, weights from the unbiased scores
normalised over the k; the layer holds ``experts_held`` of the
``num_experts`` the router knows, from ``expert_offset`` (all of them by
default; a chip's share under expert parallelism otherwise) and computes what
those give.  No shared expert, no auxiliary loss.

Model state (collection ``moe``; ``make_train_step(has_model_state=True)``
carries it as it carries ResNet's batch statistics): per expert layer the
selection bias ``[num_experts]`` float32, which no gradient reaches and which
this module never updates (zeros, as the published code initialises it; its
balancing rule is a training recipe), and ``load`` ``[num_experts]`` int32,
the rows each expert was sent in the last step.

Every layer is a ``jax.checkpoint`` that saves its input and, of an expert
layer, what its backward reads of the route (``ops.moe.ROUTED``: the
selection, the selected scores, the weights, the pairs' order and its
inverse and the rows of each expert held, 1.4 MB at 16,384 tokens), so that
it routes once a step and not twice.  bf16 matmuls with float32 norms, router
scores, rotations and loss.

Named scopes (metadata, like ``training.PHASE_SCOPES``): ``apex.moe`` around
the expert layer with ``apex.moe.route``, ``apex.moe.experts`` and
``apex.moe.combine`` inside it (``ops.moe.MOE_SCOPES``), ``apex.sconv``
around the short conv with its projections, ``apex.rope`` around the per-head
norm and the rotation.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..amp.policy import default_norm_predicate
from ..normalization import RMSNorm
from ..ops.flash_attention import flash_attention
from ..ops.moe import MOE_SCOPES, ROUTED, moe_layer
from ..ops.rope import qk_norm_rope
from ..ops.short_conv import gated_short_conv
from .granite_hybrid import head_logits

#: the scopes of the mixers; the expert layer's are ``ops.moe.MOE_SCOPES``
SCONV_SCOPE, ROPE_SCOPE = "apex.sconv", "apex.rope"

#: the published list of layer kinds: two leading conv layers (the dense
#: ones), then nine periods of attention, conv, conv, conv, then attention, conv
LAYER_TYPES = (("conv",) * 2 + ("full_attention", "conv", "conv", "conv") * 9
               + ("full_attention", "conv"))

_dense_init = nn.initializers.normal(0.02)


def keep_fp32(path: str) -> bool:
    """``make_train_step(norm_predicate=keep_fp32)``: what amp O2 leaves in
    float32 in this model: the norm weights and the router (its scores decide
    a selection; ``ops.moe.route`` refuses it in any other dtype, so a caller
    that forgets this fails at the first trace)."""
    return default_norm_predicate(path) or "router" in path.split("/")


def _conv_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1/sqrt(taps), the depthwise ``nn.Conv1d`` default."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dense(features, dtype, name):
    return nn.DenseGeneral(features, axis=-1, use_bias=False, dtype=dtype,
                           kernel_init=_dense_init, name=name)


class ShortConv(nn.Module):
    conv_taps: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        with jax.named_scope(SCONV_SCOPE):
            w_in = self.param("in_proj", _dense_init, (d, 3 * d), jnp.float32)
            taps = self.param("conv_kernel", _conv_init, (self.conv_taps, d),
                              jnp.float32)
            w_out = self.param("out_proj", _dense_init, (d, d), jnp.float32)
            # [batch, 1, channels, tokens] from here to out_proj: what the
            # conv's shifted views read, so nothing in between is re-tiled
            bcx = jnp.einsum("btd,de->bet", x.astype(self.dtype),
                             w_in.astype(self.dtype))[:, None]
            gate_in, gate_out, xs = jnp.split(bcx, 3, axis=2)
            y = gated_short_conv(gate_in, gate_out, xs,
                                 taps.astype(jnp.float32))
            return jnp.einsum("bet,ed->btd", y[:, 0], w_out.astype(self.dtype))


class RopeAttention(nn.Module):
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        proj = lambda name, heads: nn.DenseGeneral(
            (heads, self.head_dim), use_bias=False, dtype=self.dtype,
            kernel_init=_dense_init, name=name)(x)
        q, k = proj("query", self.num_heads), proj("key", self.num_kv_heads)
        with jax.named_scope(ROPE_SCOPE):
            weight = lambda name: self.param(
                name, nn.initializers.ones, (self.head_dim,), jnp.float32)
            q, k = qk_norm_rope(q, k, weight("q_norm"), weight("k_norm"),
                                theta=self.rope_theta, eps=self.eps)
        ctx = flash_attention(q, k, proj("value", self.num_kv_heads),
                              causal=True, sm_scale=self.head_dim ** -0.5)
        return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, kernel_init=_dense_init,
                               name="out")(ctx.astype(self.dtype))


class DenseMLP(nn.Module):
    width: int = 11776
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate, up = (_dense(self.width, self.dtype, name)(x)
                    for name in ("w1", "w3"))
        act = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        return _dense(x.shape[-1], self.dtype, "w2")(act.astype(gate.dtype))


class RoutedExperts(nn.Module):
    """``ops.moe_layer`` with its parameters and its state.  ``experts_held``
    of the router's ``num_experts`` live here, from ``expert_offset``."""
    width: int = 1536
    num_experts: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    top_k: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, g = x.shape[-1], self.experts_held
        router = self.param("router", _dense_init, (d, self.num_experts),
                            jnp.float32)
        w1, w3 = (self.param(name, _dense_init, (g, d, self.width),
                             jnp.float32) for name in ("w1", "w3"))
        w2 = self.param("w2", _dense_init, (g, self.width, d), jnp.float32)
        bias = self.variable("moe", "selection_bias", jnp.zeros,
                             (self.num_experts,), jnp.float32)
        load = self.variable("moe", "load", jnp.zeros, (self.num_experts,),
                             jnp.int32)
        with jax.named_scope(MOE_SCOPES[0]):
            y, counts, sel, walked = moe_layer(
                x.astype(self.dtype), router, bias.value,
                *(w.astype(self.dtype) for w in (w1, w3, w2)),
                top_k=self.top_k, expert_offset=self.expert_offset,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor)
        if not self.is_initializing() and self.is_mutable_collection("moe"):
            load.value = counts
        # read by a caller that asks for "intermediates"; nothing otherwise
        self.sow("intermediates", "selected", sel)
        self.sow("intermediates", "rows_walked", walked)
        return y


class Lfm2Layer(nn.Module):
    """One layer: the operator of its kind, then its feed-forward (``ff``:
    the constructor arguments of ``DenseMLP`` or of ``RoutedExperts``), each
    behind an RMSNorm and added to the residual stream."""
    kind: str
    operator: Any                   # the operator's constructor arguments
    routed: bool
    ff: Any
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        if self.kind == "conv":
            operator = ShortConv(**self.operator, dtype=self.dtype, name="conv")
        elif self.kind == "full_attention":
            operator = RopeAttention(**self.operator, eps=self.eps,
                                     dtype=self.dtype, name="attention")
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        h = h + operator(RMSNorm(self.eps, name="operator_norm")(h)
                         ).astype(h.dtype)
        ff = (RoutedExperts(**self.ff, dtype=self.dtype, name="experts")
              if self.routed else
              DenseMLP(**self.ff, dtype=self.dtype, name="mlp"))
        return h + ff(RMSNorm(self.eps, name="ffn_norm")(h)).astype(h.dtype)


class Lfm2Moe(nn.Module):
    """``__call__(input_ids) -> logits [B, T, V]`` (float32, tied head).
    The defaults are LFM2-24B-A2B's published ``config.json``."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Sequence[str] = LAYER_TYPES
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 11776
    moe_dim: int = 1536
    num_experts: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    top_k: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_taps: int = 3
    rope_theta: float = 1e6
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, input_ids):
        wte = self.param("wte", _dense_init,
                         (self.vocab_size, self.hidden_size), jnp.float32)
        operators = {
            "conv": dict(conv_taps=self.conv_taps),
            "full_attention": dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.hidden_size // self.num_heads,
                rope_theta=self.rope_theta)}
        experts = dict(
            width=self.moe_dim, num_experts=self.num_experts,
            experts_held=self.experts_held, expert_offset=self.expert_offset,
            top_k=self.top_k, norm_topk_prob=self.norm_topk_prob,
            routed_scaling_factor=self.routed_scaling_factor)
        h = wte[input_ids].astype(self.dtype)
        # saves the layer's input and what an expert layer's backward reads
        # of the route
        layer = nn.remat(Lfm2Layer, policy=(
            jax.checkpoint_policies.save_only_these_names(ROUTED)))
        for i, kind in enumerate(self.layer_types):
            routed = i >= self.num_dense_layers
            h = layer(kind, operators.get(kind), routed,
                      experts if routed else dict(width=self.mlp_dim),
                      self.eps, self.dtype, name=f"layer_{i}")(h)
        h = RMSNorm(self.eps, name="norm_f")(h)
        return head_logits(h, wte)


def lfm2_moe_tiny(**kw):
    """The same block at toy widths: one dense layer and one period."""
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("layer_types", LAYER_TYPES[:1] + LAYER_TYPES[2:6])
    kw.setdefault("num_dense_layers", 1)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("mlp_dim", 160)
    kw.setdefault("moe_dim", 32)
    kw.setdefault("num_experts", 8)
    kw.setdefault("experts_held", kw["num_experts"])
    return Lfm2Moe(**kw)
