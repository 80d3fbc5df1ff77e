"""SmallThinker (``smallthinker``) in plain float32: every layer is

    n1 = RMSNorm1(h); h = h + attn(n1); n2 = RMSNorm2(h); h = h + MoE(n2; n1)

with ``attn`` GQA softmax attention whose scores are **exact over all keys**
under a mask (``0 <= i - j < window`` on a window layer, ``j <= i`` on a
global one), computed a block of queries at a time (a ``[heads, 16384,
16384]`` score array is 30 GB; each block is a checkpoint, so the backward
holds one block's scores), rotary positions on q and k where the layer's
``rope_layout`` entry is 1 (half-split, angles and their sines and cosines
in float64 on the host); and the expert layer as a **dense loop over the
experts held** with a 0/1 mask of the selection (no sort, no grouped
matmul)::

    l   = W_g n1                                  # the router reads n1
    sel = top_k(l);  w = softmax(l[sel])          # over the k selected
    y   = sum_{e in sel, e held} w_e W2_e (relu(W1_e n2) * W3_e n2)

then a final RMSNorm, an untied head and mean next-token cross entropy (in
blocks of tokens, each a checkpoint).

``cfg`` is the configuration file's own keys (``rms_norm_eps``,
``moe_num_active_primary_experts``, ``expert_offset``, ``rope_theta``,
``sliding_window_size``, ``sliding_window_layout``, ``rope_layout``).  A
layer's kind is read from the two lists at its index; the router's width and
the experts held from the parameters' shapes.  With everything held this is
the uncut layer; with a share held, what the absent experts would add is
left out, as in the program.

``forced`` (by layer, ``[tokens, k]``) makes the expert layers apply a given
selection in place of their own: the weights are still the softmax of the
reference's own logits of those experts, and the selection reported is still
the reference's own.  ``select`` is the router alone, on a given input.

``loss_and_grads`` differentiates the whole model at once (small sizes);
``loss_and_grads_by_layer`` does the same arithmetic one layer at a time and
hands every layer's gradient to the host before the next.  Both also return
the reference's routing: per layer the selection ``[tokens, k]`` and the
rows sent to each of the router's experts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: queries a block of the attention, and tokens a block of the head
_Q_BLOCK = 512
_HEAD_BLOCK = 4096


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _block(n, most):
    """The largest divisor of ``n`` that is at most ``most``."""
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def _rotation(t, head_dim, theta):
    """``(cos, sin)`` of the rotary angles ``pos * theta ** (-2 i / d)``,
    ``[t, 1, head_dim / 2]`` float32, computed in float64."""
    inv = float(theta) ** (-2.0 * np.arange(head_dim // 2) / head_dim)
    angles = np.arange(t, dtype=np.float64)[:, None] * inv
    return (jnp.asarray(np.cos(angles)[:, None], jnp.float32),
            jnp.asarray(np.sin(angles)[:, None], jnp.float32))


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, cfg, windowed, rotated):
    b, t, _ = x.shape
    q, k, v = (jnp.einsum("btd,dhk->bthk", x, p[name]["kernel"])
               for name in ("query", "key", "value"))
    if rotated:
        cos, sin = _rotation(t, q.shape[-1], cfg["rope_theta"])
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scale = q.shape[-1] ** -0.5
    window = cfg["sliding_window_size"] if windowed else None
    bq = _block(t, _Q_BLOCK)

    def one(args):
        start, qb = args
        i = start + jnp.arange(bq)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        scores = jnp.einsum("bqhk,bshk->bhqs", qb, k) * scale
        att = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshk->bqhk", att, v)

    blocks = jnp.moveaxis(q.reshape((b, t // bq, bq) + q.shape[2:]), 1, 0)
    ctx = jax.lax.map(jax.checkpoint(one),
                      (jnp.arange(t // bq) * bq, blocks))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(q.shape)
    return jnp.einsum("bqhk,hkd->bqd", ctx, p["out"]["kernel"])


def _select(u, router, cfg):
    """``(logits, sel)`` of the router's input ``u``: ``[N, D]``."""
    logits = u @ router
    return logits, jax.lax.top_k(logits,
                                 cfg["moe_num_active_primary_experts"])[1]


def _experts(x, u, p, cfg, forced=None):
    """``(out, (sel, counts))``: every expert held applied to every token of
    ``x``, weighted by that token's weight for it (zero where it was not
    selected); the router reads ``u``.  ``sel`` and ``counts`` are the
    reference's own whatever was applied."""
    lead, x = x.shape[:-1], x.reshape(-1, x.shape[-1])
    u = u.reshape(-1, u.shape[-1])
    routed = p["router"].shape[1]
    logits, own = _select(u, p["router"], cfg)
    sel = own if forced is None else forced
    weights = jax.nn.softmax(jnp.take_along_axis(logits, sel, axis=-1), -1)
    out = jnp.zeros_like(x)
    for i in range(p["w1"].shape[0]):
        mine = (sel == cfg["expert_offset"] + i).astype(x.dtype)   # 0/1
        y = (jax.nn.relu(x @ p["w1"][i]) * (x @ p["w3"][i])) @ p["w2"][i]
        out = out + (weights * mine).sum(-1, keepdims=True) * y
    counts = (own[..., None] == jnp.arange(routed)).sum((0, 1))
    return out.reshape(lead + x.shape[-1:]), (own, counts.astype(jnp.int32))


def _kind(cfg, name):
    """``(windowed, rotated)`` of the layer ``name`` by its index."""
    i = int(name.split("_")[1])
    return (bool(cfg["sliding_window_layout"][i]),
            bool(cfg["rope_layout"][i]))


def layer(p, h, cfg, kind, forced=None):
    """``(h', routing)`` of one layer of ``kind`` (``_kind``)."""
    eps = cfg["rms_norm_eps"]
    n1 = _rms_norm(h, p["input_norm"]["scale"], eps)
    h = h + _attention(n1, p["attention"], cfg, *kind)
    n2 = _rms_norm(h, p["post_attention_norm"]["scale"], eps)
    out, routing = _experts(n2, n1, p["experts"], cfg, forced)
    return h + out, routing


def _head(top, h, y, cfg):
    """Mean cross entropy from the last layer's output; ``top`` holds
    ``norm_f`` and the untied ``head`` ``[vocabulary, hidden]``."""
    h = _rms_norm(h, top["norm_f"]["scale"], cfg["rms_norm_eps"])
    n, d = h.shape[0] * h.shape[1], h.shape[-1]
    bt = _block(n, _HEAD_BLOCK)

    def part(args):
        hb, yb = args
        logp = jax.nn.log_softmax(hb @ top["head"].T, axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1).sum()

    parts = jax.lax.map(jax.checkpoint(part), (h.reshape(n // bt, bt, d),
                                               y.reshape(n // bt, bt)))
    return parts.sum() / n


def _layer_names(params):
    return sorted((name for name in params if name.startswith("layer_")),
                  key=lambda name: int(name.split("_")[1]))


def _forced(forced, name):
    return None if forced is None else jnp.asarray(forced[name], jnp.int32)


def loss(params, x, y, cfg, forced=None):
    """``(mean cross entropy, routing by layer)`` of next tokens ``y`` given
    ``x`` (``[batch, seq]``)."""
    with jax.default_matmul_precision("highest"):
        h, routing = params["wte"][x], {}
        for name in _layer_names(params):
            h, routing[name] = layer(params[name], h, cfg, _kind(cfg, name),
                                     _forced(forced, name))
        return _head(params, h, y, cfg), routing


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


_READ = ("rms_norm_eps", "moe_num_active_primary_experts", "expert_offset",
         "rope_theta", "sliding_window_size", "sliding_window_layout",
         "rope_layout")


def _hashable(cfg):
    """What the layers read of the configuration, as a static argument."""
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in _READ)


def _routing(routing):
    return {name: {"selected": np.asarray(sel), "counts": np.asarray(counts)}
            for name, (sel, counts) in routing.items()}


@functools.partial(jax.jit, static_argnums=4)
def _whole(params, x, y, forced, cfg):
    return jax.value_and_grad(loss, has_aux=True)(params, x, y, dict(cfg),
                                                  forced)


def loss_and_grads(params, x, y, cfg, forced=None):
    """``(loss, gradients, routing)``, the whole model differentiated at
    once."""
    # the batch is an argument: a closed-over array would be a constant of
    # the program, and every seed would compile anew
    (value, routing), grads = _whole(_f32(params), x, y, forced,
                                     _hashable(cfg))
    return value, grads, _routing(routing)


@functools.partial(jax.jit, static_argnums=2)
def _select_jit(u, router, cfg):
    with jax.default_matmul_precision("highest"):
        return _select(u, router, dict(cfg))[1]


def select(u, router, cfg):
    """The reference's selection ``[tokens, k]`` for the router's input
    ``u`` (``[..., hidden]``, any float dtype)."""
    u = jnp.asarray(u, jnp.float32)
    return np.asarray(_select_jit(u.reshape(-1, u.shape[-1]),
                                  jnp.asarray(router, jnp.float32),
                                  _hashable(cfg)))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_fwd(p, h, forced, cfg, kind):
    with jax.default_matmul_precision("highest"):
        return layer(p, h, dict(cfg), kind, forced)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _layer_bwd(p, h, forced, dh, cfg, kind):
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda p_, h_: layer(p_, h_, dict(cfg), kind,
                                              forced)[0], p, h)
        return vjp(dh)


@functools.partial(jax.jit, static_argnums=3)
def _head_bwd(top, h, y, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda top_, h_: _head(top_, h_, y, dict(cfg)), argnums=(0, 1))(
                top, h)


@jax.jit
def _embed_bwd(wte, x, dh):
    return jax.vjp(lambda w: w[x], wte)[1](dh)[0]


def loss_and_grads_by_layer(params, x, y, cfg, forced=None):
    """``(loss, gradients, routing)`` with the gradients as numpy arrays on
    the host, in the tree of ``params``.  Forward keeps every layer's input
    (the residual stream); backward walks the layers from the last, one
    program per layer kind."""
    kinds = {name: _kind(cfg, name) for name in _layer_names(params)}
    cfg = _hashable(cfg)
    inputs, routing = [], {}
    h = jnp.asarray(params["wte"], jnp.float32)[x]
    for name, kind in kinds.items():
        inputs.append(h)
        h, routing[name] = _layer_fwd(_f32(params[name]), h,
                                      _forced(forced, name), cfg, kind)
    top = _f32({"head": params["head"], "norm_f": params["norm_f"]})
    value, (d_top, dh) = _head_bwd(top, h, y, cfg)
    grads = {"norm_f": jax.device_get(d_top["norm_f"]),
             "head": jax.device_get(d_top["head"])}
    for name in reversed(list(kinds)):
        d_layer, dh = _layer_bwd(_f32(params[name]), inputs.pop(),
                                 _forced(forced, name), dh, cfg, kinds[name])
        grads[name] = jax.device_get(d_layer)
    grads["wte"] = np.asarray(_embed_bwd(
        jnp.asarray(params["wte"], jnp.float32), x, dh))
    return value, {name: grads[name] for name in params}, _routing(routing)
