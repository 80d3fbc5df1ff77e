"""SmallThinker style decoder: sliding-window layers with rotary positions
beside global layers without positions, by two published lists, each layer
an attention and a layer of routed ReGLU experts whose router reads the
attention's input (flax, TPU-first).

The architecture of ``PowerInfer/SmallThinker-21BA3B-Instruct``, which the
defaults below spell out::

    h = wte[ids]
    per layer:  n1 = RMSNorm1(h)
                h  = h + attn_l(n1)                  # by the two layout lists
                n2 = RMSNorm2(h)
                h  = h + MoE(n2; the router reads n1)
    logits = RMSNorm(h) @ head^T                     # untied, float32

Attention: 28 query and 4 KV heads of 128, no bias, scores scaled by
``head_dim ** -0.5``, causal, through ``ops.flash_attention``.  Layer ``l``
is a window layer where ``sliding_window_layout[l]`` is 1 (each query sees
the last ``sliding_window_size`` keys, itself included: the kernel's bounded
grid) and rotates q and k (:func:`apex_tpu.ops.apply_rope`, theta
``rope_theta``, the whole head) where ``rope_layout[l]`` is 1; the published
lists agree (``[0, 1, 1, 1]`` repeated: a global layer without positions,
then three window layers with them), and both are passed through as given.
Routed experts (:func:`apex_tpu.ops.moe_layer` with ``score="softmax"``,
``activation="relu"``)::

    l   = W_g n1                                 # float32 logits, [64]
    sel = top_6(l);  w = softmax(l[sel])         # renormalised over the six
    y   = sum_{e in sel, e held here} w_e W2_e (relu(W1_e n2) * W3_e n2)

No shared expert, no dense layer, no auxiliary loss.

**What is held here.**  ``experts_held`` of the router's ``num_experts``
from ``expert_offset``, and ``vocab_size`` rows of the embedding and of the
head.  The router keeps its width and its experts a token whatever is held;
attention and the norms are whole.  With a share held the experts' part goes
on to the next layer as it is: nothing stands in for the other chips.

Model state (collection ``moe``; ``make_train_step(has_model_state=True)``
carries it): per layer ``load`` ``[num_experts]`` int32, the rows each expert
was sent in the last step (those of the experts held add up to the rows
held).  The selection is sown as the intermediate ``selected``, and what the
router read as ``router_in``.

Every layer is a ``jax.checkpoint`` that saves its input and what its
expert layer's backward reads of the route (``ops.moe.ROUTED``), so that it
routes once a step.  bf16 matmuls with float32 norms, router logits,
rotations and loss.

Named scopes (metadata, like ``training.PHASE_SCOPES``): ``apex.attn.window``
and ``apex.attn.full`` around a layer's ``flash_attention`` call by its kind,
``apex.rope`` around the rotation, ``apex.moe`` around the expert layer with
``ops.moe.MOE_SCOPES`` inside it.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..amp.policy import default_norm_predicate
from ..normalization import RMSNorm
from ..ops.flash_attention import flash_attention
from ..ops.moe import MOE_SCOPES, ROUTED, moe_layer
from ..ops.rope import apply_rope
from .granite_hybrid import head_logits

#: the scopes around a layer's attention kernel, by kind; the rotation is
#: under ``ROPE_SCOPE``
WINDOW_SCOPE, FULL_SCOPE, ROPE_SCOPE = ("apex.attn.window", "apex.attn.full",
                                        "apex.rope")

#: the published lists: one global layer without positions, then three
#: window layers with rotary positions, thirteen times
SLIDING_WINDOW_LAYOUT = (0, 1, 1, 1) * 13
ROPE_LAYOUT = (0, 1, 1, 1) * 13

_dense_init = nn.initializers.normal(0.02)
#: the embedding's: ``nn.Embedding``'s own normal(0, 1).  At 0.02, as the
#: matrices, the residual stream is a few hundredths wide, the first
#: layers' outputs outweigh it, and by the third layer 87 to 93% of the
#: router's input is one vector that every token shares (every router sends
#: its tokens to the same few experts, and a sequence's ids decide which);
#: at 1 it stays 2 to 14% (``PERF.md`` section 6, PR 38)
_embed_init = nn.initializers.normal(1.0)


def keep_fp32(path: str) -> bool:
    """``make_train_step(norm_predicate=keep_fp32)``: what amp O2 leaves in
    float32 in this model: the norm weights and the router (its logits
    decide a selection; ``ops.moe.route`` refuses it in any other dtype)."""
    return default_norm_predicate(path) or "router" in path.split("/")


class Attention(nn.Module):
    """GQA attention of one kind: ``window`` keys or all of them, rotary
    positions or none."""
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    window: Optional[int] = None
    rope_theta: Optional[float] = None      # None: no positions
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        proj = lambda name, heads: nn.DenseGeneral(
            (heads, self.head_dim), use_bias=False, dtype=self.dtype,
            kernel_init=_dense_init, name=name)(x)
        q, k = proj("query", self.num_heads), proj("key", self.num_kv_heads)
        if self.rope_theta is not None:
            with jax.named_scope(ROPE_SCOPE):
                q = apply_rope(q, theta=self.rope_theta)
                k = apply_rope(k, theta=self.rope_theta)
        with jax.named_scope(FULL_SCOPE if self.window is None
                             else WINDOW_SCOPE):
            ctx = flash_attention(q, k, proj("value", self.num_kv_heads),
                                  causal=True, window=self.window,
                                  sm_scale=self.head_dim ** -0.5)
        return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, kernel_init=_dense_init,
                               name="out")(ctx.astype(self.dtype))


class RoutedExperts(nn.Module):
    """``ops.moe_layer`` under a softmax router over ReGLU experts, with its
    parameters and its state.  ``experts_held`` of the router's
    ``num_experts`` live here, from ``expert_offset``."""
    width: int = 768
    num_experts: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    top_k: int = 6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, router_in):
        d, g = x.shape[-1], self.experts_held
        router = self.param("router", _dense_init, (d, self.num_experts),
                            jnp.float32)
        w1, w3 = (self.param(name, _dense_init, (g, d, self.width),
                             jnp.float32) for name in ("w1", "w3"))
        w2 = self.param("w2", _dense_init, (g, self.width, d), jnp.float32)
        load = self.variable("moe", "load", jnp.zeros, (self.num_experts,),
                             jnp.int32)
        # one materialised array for the router and for what is sown of it:
        # without the barrier the compiler may feed the router the norm's
        # float32 result, and the sown bf16 copy is not what it read
        x = x.astype(self.dtype)
        router_in = jax.lax.optimization_barrier(router_in.astype(self.dtype))
        with jax.named_scope(MOE_SCOPES[0]):
            y, counts, sel, _ = moe_layer(
                x, router, None, *(w.astype(self.dtype) for w in (w1, w3, w2)),
                top_k=self.top_k, expert_offset=self.expert_offset,
                score="softmax", activation="relu", router_in=router_in)
        if not self.is_initializing() and self.is_mutable_collection("moe"):
            load.value = counts
        # read by a caller that asks for "intermediates"; nothing otherwise:
        # the selection, and what the router read to make it
        self.sow("intermediates", "selected", sel)
        self.sow("intermediates", "router_in", router_in)
        return y


class SmallThinkerLayer(nn.Module):
    """One layer: attention on the normed input, then the experts on the
    normed sum, their router on the attention's normed input."""
    attention: Any                  # ``Attention``'s constructor arguments
    experts: Any                    # ``RoutedExperts``'s
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        n1 = RMSNorm(self.eps, name="input_norm")(h)
        h = h + Attention(**self.attention, dtype=self.dtype,
                          name="attention")(n1).astype(h.dtype)
        n2 = RMSNorm(self.eps, name="post_attention_norm")(h)
        return h + RoutedExperts(**self.experts, dtype=self.dtype,
                                 name="experts")(n2, n1).astype(h.dtype)


class SmallThinker(nn.Module):
    """``__call__(input_ids) -> logits [B, T, V]`` (float32, untied head).
    The defaults are the published ``config.json`` with everything held."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    sliding_window_layout: Sequence[int] = SLIDING_WINDOW_LAYOUT
    rope_layout: Sequence[int] = ROPE_LAYOUT
    sliding_window_size: int = 4096
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_dim: int = 768
    num_experts: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    top_k: int = 6
    rope_theta: float = 1.5e6
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, input_ids):
        if len(self.sliding_window_layout) != len(self.rope_layout):
            raise ValueError("sliding_window_layout and rope_layout give "
                             "different numbers of layers")
        wte = self.param("wte", _embed_init,
                         (self.vocab_size, self.hidden_size), jnp.float32)
        head = self.param("head", _dense_init,
                          (self.vocab_size, self.hidden_size), jnp.float32)
        experts = dict(width=self.moe_dim, num_experts=self.num_experts,
                       experts_held=self.experts_held,
                       expert_offset=self.expert_offset, top_k=self.top_k)
        h = wte[input_ids].astype(self.dtype)
        # saves the layer's input and what its backward reads of the route
        layer = nn.remat(SmallThinkerLayer, policy=(
            jax.checkpoint_policies.save_only_these_names(ROUTED)))
        for i, (windowed, rotated) in enumerate(zip(self.sliding_window_layout,
                                                    self.rope_layout)):
            attention = dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim,
                window=self.sliding_window_size if windowed else None,
                rope_theta=self.rope_theta if rotated else None)
            h = layer(attention, experts, self.eps, self.dtype,
                      name=f"layer_{i}")(h)
        h = RMSNorm(self.eps, name="norm_f")(h)
        return head_logits(h, head)


def smallthinker_tiny(**kw):
    """The same block at toy widths: one period of four layers, a window of
    16 keys."""
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("sliding_window_layout", SLIDING_WINDOW_LAYOUT[:4])
    kw.setdefault("rope_layout", ROPE_LAYOUT[:4])
    kw.setdefault("sliding_window_size", 16)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("moe_dim", 32)
    kw.setdefault("num_experts", 16)
    kw.setdefault("experts_held", kw["num_experts"])
    kw.setdefault("top_k", 4)
    return SmallThinker(**kw)
