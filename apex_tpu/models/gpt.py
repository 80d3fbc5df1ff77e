"""GPT-style decoder-only causal LM (flax, TPU-first).

Beyond-parity model family (the reference ships no model code): the
long-context training model that exercises the framework's causal flash
attention (``apex_tpu/ops/flash_attention.py``), FusedLayerNorm, the
fused xentropy loss and — through ``attention_impl="ring"`` — sequence
parallelism.  Pre-LN residual blocks, learned positions, weight-tied LM
head; bf16 matmuls with fp32 softmax/norm/loss (the O1 cast-list split,
hard-wired where it matters).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..normalization import FusedLayerNorm


class GPTBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.float32
    attention_impl: str = "flash"
    sp_axis: Optional[str] = None
    num_kv_heads: Optional[int] = None   # GQA: kv heads shared across q heads
    window: Optional[int] = None         # sliding-window local attention
    decode: bool = False                 # KV-cache single-token decode
    cache_len: int = 0
    quant: Any = None                    # ISSUE 13 int8 projection hook

    @nn.compact
    def __call__(self, x, *, kv_cache=None, positions=None):
        d = x.shape[-1]
        h = FusedLayerNorm(normalized_shape=d, name="ln1")(x).astype(x.dtype)
        from .bert import BertSelfAttention, _dense_factory
        attn = BertSelfAttention(self.num_heads, self.dtype,
                                 attention_impl=self.attention_impl,
                                 sp_axis=self.sp_axis, causal=True,
                                 num_kv_heads=self.num_kv_heads,
                                 window=self.window,
                                 decode=self.decode,
                                 cache_len=self.cache_len,
                                 quant=self.quant,
                                 name="attention")
        new_cache = None
        if kv_cache is not None:
            h, new_cache = attn(h, kv_cache=kv_cache, positions=positions)
        else:
            h = attn(h)
        x = x + h
        h = FusedLayerNorm(normalized_shape=d, name="ln2")(x).astype(x.dtype)
        mlp = _dense_factory(self.quant, self.dtype)
        h = mlp("mlp_up", self.mlp_dim)(h)
        h = nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        h = mlp("mlp_down", d)(h)
        if new_cache is not None:
            return x + h, new_cache
        return x + h


class GPT(nn.Module):
    """Decoder-only LM.  ``__call__(input_ids) -> logits [B, T, V]`` (fp32,
    weight-tied to the token embedding)."""
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    dtype: Any = jnp.float32
    attention_impl: str = "flash"   # full | blockwise | flash | ring | ulysses
    sp_axis: Optional[str] = None
    num_kv_heads: Optional[int] = None   # GQA (llama-style); None = MHA
    window: Optional[int] = None         # sliding-window local attention
    decode: bool = False                 # KV-cache autoregressive decode
    quant: Any = None                    # ISSUE 13 int8 projection hook

    @nn.compact
    def __call__(self, input_ids, *, kv_caches=None, positions=None):
        b, t = input_ids.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (self.vocab_size, self.hidden_size), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (self.max_len, self.hidden_size), jnp.float32)
        if kv_caches is not None:
            # Incremental forward over externally-owned caches (ISSUE
            # 11): ``kv_caches`` is one ``(k, v)`` dense view per layer
            # ([B, L, n_kv, head_dim] — :func:`init_cache` builds them,
            # the serving engine gathers them from its page pool) and
            # ``positions`` [B] int32 the per-sequence position of the
            # first fresh token.  T may be 1 (decode) or a prompt
            # bucket (prefill).  Returns ``(logits [B, T, V],
            # new_caches)`` — the caller owns persisting the updates.
            if len(kv_caches) != self.num_layers:
                raise ValueError(
                    f"kv_caches has {len(kv_caches)} entries for "
                    f"{self.num_layers} layers")
            if positions is None:
                positions = jnp.zeros((b,), jnp.int32)
            pos = positions[:, None] + jnp.arange(t)[None, :]    # [B, T]
            x = (wte[input_ids] + wpe[pos]).astype(self.dtype)
            new_caches = []
            for i in range(self.num_layers):
                x, c = GPTBlock(self.num_heads, self.mlp_dim, self.dtype,
                                attention_impl=self.attention_impl,
                                sp_axis=None,
                                num_kv_heads=self.num_kv_heads,
                                window=self.window,
                                quant=self.quant,
                                name=f"block_{i}")(
                                    x, kv_cache=kv_caches[i],
                                    positions=positions)
                new_caches.append(c)
            x = FusedLayerNorm(normalized_shape=self.hidden_size,
                               name="ln_f")(x)
            logits = (x.astype(jnp.float32) @ wte.T).astype(jnp.float32)
            return logits, new_caches
        # Checked at trace time — JAX gather clamps out-of-range indices,
        # so an oversized (global) sequence would silently reuse the last
        # position embedding instead of erroring.
        sp = 1 if self.sp_axis is None else jax.lax.axis_size(self.sp_axis)
        if not self.decode and sp * t > self.max_len:
            raise ValueError(
                f"global sequence {sp} shard(s) x {t} tokens = {sp * t} "
                f"exceeds max_len={self.max_len}")
        if self.decode:
            # single-token step: position = tokens consumed so far.  The
            # caller must bound total steps by max_len (generate() clamps;
            # past it, positions/cache writes saturate silently).
            if t != 1:
                raise ValueError(f"decode consumes ONE token per call, "
                                 f"got {t}")
            live_step = self.has_variable("cache", "pos_index")
            pi = self.variable("cache", "pos_index",
                               lambda: jnp.zeros((), jnp.int32))
            pos = pi.value[None]
            if live_step:           # init trace only creates the counter
                pi.value = pi.value + 1
        else:
            pos = jnp.arange(t)
            if self.sp_axis is not None:
                # Sequence-sharded: this shard's global positions.
                pos = pos + jax.lax.axis_index(self.sp_axis) * t
        x = (wte[input_ids] + wpe[pos][None]).astype(self.dtype)
        for i in range(self.num_layers):
            x = GPTBlock(self.num_heads, self.mlp_dim, self.dtype,
                         attention_impl=self.attention_impl,
                         sp_axis=self.sp_axis,
                         num_kv_heads=self.num_kv_heads,
                         window=self.window,
                         decode=self.decode,
                         cache_len=self.max_len,
                         quant=self.quant,
                         name=f"block_{i}")(x)
        x = FusedLayerNorm(normalized_shape=self.hidden_size,
                           name="ln_f")(x)
        # One product over the flattened tokens: the fused loss's ``g * r``
        # comes back as ``[B * T, V]``, and XLA fuses it into the head's two
        # gradient products only with no reshape between
        # (``granite_hybrid.head_logits``).
        b, t, d = x.shape
        logits = x.reshape(b * t, d).astype(jnp.float32) @ wte.T
        return logits.astype(jnp.float32).reshape(b, t, -1)


def gpt2_small(**kw):
    return GPT(**kw)


def gpt_tiny(**kw):
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("mlp_dim", 256)
    kw.setdefault("max_len", 256)
    return GPT(**kw)


def init_cache(model: GPT, batch_size: int, *,
               cache_len: Optional[int] = None, dtype=None):
    """Zeroed external KV-cache views for the incremental forward
    (ISSUE 11): one ``(k, v)`` pair per layer, each
    ``[batch_size, cache_len, n_kv_heads, head_dim]``.

    This is the DENSE view shape ``model.apply(..., kv_caches=...,
    positions=...)`` consumes; the serving engine's paged pool gathers
    into (and scatters out of) exactly this shape per step.  GQA models
    cache only the kv heads — the memory saving is real.  ``cache_len``
    defaults to ``model.max_len`` and must not exceed it (positions past
    it have no learned embedding).  ``dtype`` defaults to the model's
    compute dtype."""
    cache_len = model.max_len if cache_len is None else int(cache_len)
    if cache_len > model.max_len:
        raise ValueError(f"cache_len {cache_len} exceeds the model's "
                         f"max_len {model.max_len}")
    n_kv = model.num_kv_heads or model.num_heads
    head_dim = model.hidden_size // model.num_heads
    dt = model.dtype if dtype is None else dtype
    shape = (batch_size, cache_len, n_kv, head_dim)
    return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
            for _ in range(model.num_layers)]


def generate(model: GPT, params, prompt_ids, max_new_tokens: int, *,
             temperature: float = 0.0, rng=None):
    """Autoregressive generation with a KV cache (r3; the reference has no
    model/inference code — SURVEY §5 long-context scope).

    One compiled ``lax.scan`` drives both prefill and generation: each
    step feeds one token (teacher-forced from the prompt while it lasts,
    sampled afterwards) through the ``decode=True`` clone of ``model``,
    whose per-layer caches live in a flax "cache" collection threaded as
    scan carry.  Greedy when ``temperature == 0``, else softmax sampling.

    Returns ``[B, P + max_new_tokens]`` token ids (prompt included),
    truncated at ``model.max_len``.
    """
    import jax.random as jrandom

    if model.sp_axis is not None:
        raise ValueError("generate() decodes full sequences; build the "
                         "model without sp_axis for inference")
    dec = model.clone(decode=True)
    b, p = prompt_ids.shape
    total = min(p + max_new_tokens, model.max_len)
    if rng is None:
        rng = jrandom.PRNGKey(0)

    # cache buffers are zeros by construction — build them from shapes
    # only (a real dec.init would PRNG-initialize a full second parameter
    # set just to throw it away)
    shapes = jax.eval_shape(dec.init, jrandom.PRNGKey(0),
                            jnp.zeros((b, 1), jnp.int32))["cache"]
    cache0 = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    prompt = jnp.asarray(prompt_ids)

    def step(carry, t):
        cache, tok, key = carry
        logits, upd = dec.apply({"params": params, "cache": cache},
                                tok[:, None], mutable=["cache"])
        logits = logits[:, 0]                       # [B, V]
        key, sub = jrandom.split(key)
        if temperature == 0.0:
            sampled = jnp.argmax(logits, axis=-1)
        else:
            sampled = jrandom.categorical(sub, logits / temperature,
                                          axis=-1)
        # teacher-force while the prompt lasts: the NEXT input token
        in_prompt = t + 1 < p
        nxt = jnp.where(
            in_prompt,
            prompt[:, jnp.minimum(t + 1, p - 1)],
            sampled)
        return (upd["cache"], nxt, key), nxt

    (_, _, _), toks = jax.lax.scan(
        step, (cache0, prompt[:, 0], rng), jnp.arange(total - 1))
    return jnp.concatenate([prompt[:, :1], toks.T], axis=1)
