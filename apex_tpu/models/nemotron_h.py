"""Nemotron-H style decoder with latent experts: layers that are each a
Mamba-2 mixer, a GQA attention or a latent expert layer **alone**, by a
string of letters (flax, TPU-first).

The architecture of ``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``
(``model_type`` ``nemotron_h``), which the defaults below spell out::

    h = wte[ids]
    per layer:  h = h + f_l(RMSNorm_l(h))        # by pattern[l]: M, * or E
    logits = RMSNorm(h) @ head^T                 # untied, float32

``M``, Mamba-2: :class:`apex_tpu.models.granite_hybrid.Mamba2Mixer` at 128
heads of 64, 8 groups of B and C, state 128, chunk 128; the gated norm is
over each group's channels.  ``*``, attention: 32 query and 2 KV heads of
128, causal, scores scaled by ``head_dim ** -0.5``, no bias and **no
positions** (the published attention applies no rotary embedding), through
``ops.flash_attention`` and its shape dispatch.  ``E``, the latent expert
layer (:func:`apex_tpu.ops.latent_moe_layer`), with ``u`` the normed hidden
state::

    s   = sigmoid(W_g u)                         # float32: the router reads u
    sel = top_k(s + b);  w = scale * s[sel] / (sum s[sel] + 1e-6)
    l   = W_down u                               # [latent]
    r   = sum_{e in sel, e held here} w_e W2_e relu(W1_e l)^2
    y   = W_up r + V2 relu(V1 u)^2               # the shared expert, on u

**What is held here.**  The constructor says which share of each layer this
model holds: ``mamba_heads`` and ``mamba_groups`` (a tensor-parallel rank
holds whole groups with their heads, so the grouped norm is local),
``num_heads`` and ``num_kv_heads``, ``experts_held`` of the router's
``num_experts`` from ``expert_offset``, ``vocab_size`` rows of the embedding
and of the head.  The router keeps its width and its experts a token
whatever is held; the latent projections, the shared expert and every norm
are whole.  With a share held the out-projections give partial sums, which go
on to the next layer as they are: nothing stands in for the other chips.

Model state (collection ``moe``; ``make_train_step(has_model_state=True)``
carries it): per expert layer the correction bias ``[num_experts]`` float32,
which no gradient reaches and which this module never updates (zeros),
``load`` ``[num_experts]`` int32, the rows each expert was sent in the last
step (those of the experts held add up to the rows held), and
``rows_computed`` int32, the rows the waves of the expert chain went over
(the rows held, rounded up to a wave).  The selection is sown as the
intermediate ``selected``.

Every layer is a ``jax.checkpoint`` that saves its input and, of a latent
layer, the routed result in the latent space (32 MB at 16,384 tokens), so
that the waves run twice a step and not three times, and what its backward
reads of the route (``ops.moe.ROUTED``: the selection, the selected scores,
the weights, the pairs' order and the rows of each expert held, 6.1 MB at
16,384 tokens), so that it routes once a step and not twice.  bf16 matmuls
with float32 norms, decays, router scores and loss.

Named scopes (metadata, like ``training.PHASE_SCOPES``): ``apex.moe`` around
the latent layer with ``apex.moe.route``, ``apex.moe.experts``,
``apex.moe.combine``, ``apex.moe.latent`` (the two latent projections) and
``apex.moe.shared`` (the shared expert) inside it; ``apex.ssm*`` as the
mixer has them.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..normalization import RMSNorm
from ..ops.moe import LATENT_SCOPES, MOE_SCOPES, ROUTED, latent_moe_layer
from . import granite_hybrid
from .granite_hybrid import GQAttention, Mamba2Mixer, head_logits

#: the published string of layer kinds: 40 ``M``, 40 ``E``, 8 ``*``
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")

_dense_init = nn.initializers.normal(0.02)
_LATENT, _SHARED = LATENT_SCOPES

#: the name under which a latent layer's routed result (``[tokens, latent]``
#: in the compute dtype) is saved by its layer's checkpoint
MIXED = "apex.moe.mixed"


def keep_fp32(path: str) -> bool:
    """``make_train_step(norm_predicate=keep_fp32)``: what amp O2 leaves in
    float32 in this model: the norm weights, the mixer's ``A_log``,
    ``dt_bias`` and ``D``, and the router (its scores decide a selection;
    ``ops.moe.route`` refuses it in any other dtype)."""
    return granite_hybrid.keep_fp32(path) or "router" in path.split("/")


def _relu2(x):
    """``relu(x) ** 2`` in float32, rounded once."""
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(x.dtype)


class LatentExperts(nn.Module):
    """``ops.latent_moe_layer`` between its two latent projections, beside
    the shared expert, with its parameters and its state.  ``experts_held``
    of the router's ``num_experts`` live here, from ``expert_offset``."""
    latent_size: int = 1024
    width: int = 2688
    shared_width: int = 5376
    num_experts: int = 512
    experts_held: int = 512
    expert_offset: int = 0
    top_k: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, g, lat = x.shape[-1], self.experts_held, self.latent_size
        matrix = lambda name, *shape: self.param(name, _dense_init, shape,
                                                 jnp.float32)
        router = matrix("router", d, self.num_experts)
        down, up = matrix("latent_down", d, lat), matrix("latent_up", lat, d)
        w1 = matrix("w1", g, lat, self.width)
        w2 = matrix("w2", g, self.width, lat)
        v1 = matrix("shared_w1", d, self.shared_width)
        v2 = matrix("shared_w2", self.shared_width, d)
        bias = self.variable("moe", "correction_bias", jnp.zeros,
                             (self.num_experts,), jnp.float32)
        load = self.variable("moe", "load", jnp.zeros, (self.num_experts,),
                             jnp.int32)
        computed = self.variable("moe", "rows_computed", jnp.zeros, (),
                                 jnp.int32)
        cast = lambda w: w.astype(self.dtype)
        x = x.astype(self.dtype)
        with jax.named_scope(MOE_SCOPES[0]):
            with jax.named_scope(_LATENT):
                latent = x @ cast(down)
            mixed, counts, sel, rows = latent_moe_layer(
                x, latent, router, bias.value, cast(w1), cast(w2),
                top_k=self.top_k, expert_offset=self.expert_offset,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor)
            # kept by the layer's checkpoint: the recomputed forward then has
            # no use for the waves, which would run a third time for it
            mixed = checkpoint_name(mixed, MIXED)
            with jax.named_scope(_LATENT):
                y = mixed @ cast(up)
            with jax.named_scope(_SHARED):
                y = y + _relu2(x @ cast(v1)) @ cast(v2)
        if not self.is_initializing() and self.is_mutable_collection("moe"):
            load.value, computed.value = counts, rows[1]
        # read by a caller that asks for "intermediates"; nothing otherwise:
        # the selection, and what the router read to make it
        self.sow("intermediates", "selected", sel)
        self.sow("intermediates", "router_in", x)
        return y


class NemotronLayer(nn.Module):
    """One layer: a mixer or a feed-forward part alone, behind an RMSNorm
    and added to the residual stream.  ``part``: the constructor arguments of
    the module of its ``kind``."""
    kind: str
    part: Any
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        if self.kind == "M":
            f = Mamba2Mixer(**self.part, eps=self.eps, dtype=self.dtype,
                            name="mamba")
        elif self.kind == "*":
            f = GQAttention(**self.part, dtype=self.dtype, name="attention")
        elif self.kind == "E":
            f = LatentExperts(**self.part, dtype=self.dtype, name="experts")
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}")
        return h + f(RMSNorm(self.eps, name="norm")(h)).astype(h.dtype)


class NemotronH(nn.Module):
    """``__call__(input_ids) -> logits [B, T, V]`` (float32, untied head).
    The defaults are the published ``config.json`` with everything held."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = PATTERN
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 8
    mamba_conv: int = 4
    mamba_chunk: int = 128
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    latent_size: int = 1024
    moe_dim: int = 2688
    shared_dim: int = 5376
    num_experts: int = 512
    experts_held: int = 512
    expert_offset: int = 0
    top_k: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, input_ids):
        wte = self.param("wte", _dense_init,
                         (self.vocab_size, self.hidden_size), jnp.float32)
        head = self.param("head", _dense_init,
                          (self.vocab_size, self.hidden_size), jnp.float32)
        parts = {
            "M": dict(num_heads=self.mamba_heads, head_dim=self.mamba_head_dim,
                      state_size=self.mamba_state, n_groups=self.mamba_groups,
                      conv_width=self.mamba_conv, chunk_size=self.mamba_chunk),
            "*": dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                      head_dim=self.head_dim, sm_scale=self.head_dim ** -0.5),
            "E": dict(latent_size=self.latent_size, width=self.moe_dim,
                      shared_width=self.shared_dim,
                      num_experts=self.num_experts,
                      experts_held=self.experts_held,
                      expert_offset=self.expert_offset, top_k=self.top_k,
                      norm_topk_prob=self.norm_topk_prob,
                      routed_scaling_factor=self.routed_scaling_factor)}
        h = wte[input_ids].astype(self.dtype)
        # saves the layer's input and, of a latent layer, its routed result
        # and what its backward reads of the route
        layer = nn.remat(NemotronLayer, policy=(
            jax.checkpoint_policies.save_only_these_names(MIXED, ROUTED)))
        for i, kind in enumerate(self.pattern):
            h = layer(kind, parts.get(kind), self.eps, self.dtype,
                      name=f"layer_{i}")(h)
        h = RMSNorm(self.eps, name="norm_f")(h)
        return head_logits(h, head)


def nemotron_h_tiny(**kw):
    """The same block at toy widths: the first period of eleven layers."""
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("pattern", PATTERN[:11])
    kw.setdefault("mamba_heads", 8)
    kw.setdefault("mamba_head_dim", 16)
    kw.setdefault("mamba_state", 16)
    kw.setdefault("mamba_groups", 2)
    kw.setdefault("mamba_chunk", 16)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("latent_size", 32)
    kw.setdefault("moe_dim", 48)
    kw.setdefault("shared_dim", 96)
    kw.setdefault("num_experts", 16)
    kw.setdefault("experts_held", kw["num_experts"])
    kw.setdefault("top_k", 4)
    return NemotronH(**kw)
