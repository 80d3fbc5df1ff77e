"""LFM2-MoE (``lfm2_moe``) in plain float32: gated short convolutions as three
shifted products, GQA softmax attention with an explicit rotation of q and k
behind a per-head RMSNorm, a dense SwiGLU MLP in the leading layers and,
after them, routed experts as a **dense loop over the experts held** with a
0/1 mask of the reference's own selection (no sort, no grouped matmul),
RMSNorm, tied head, mean next-token cross entropy.

``cfg`` is the configuration file's own keys (``norm_eps``,
``rope_parameters``, ``num_experts_per_tok``, ``expert_offset``, ...).  A
layer's kinds are read from its parameters; the router's width, the number
of experts held and the number of taps from their shapes.  What the experts
that are not held would add is left out, as in the program.  ``state`` is the
program's ``moe`` collection (the selection bias of every expert layer);
``None`` stands for zeros.

``loss_and_grads`` differentiates the whole model at once (small sizes);
``loss_and_grads_by_layer`` does the same arithmetic one layer at a time and
hands every layer's gradient to the host before the next, so that the
published widths fit one chip beside nothing else.  Both also return the
reference's routing: per expert layer the selection ``[tokens, k]`` and the
rows sent to each of the router's experts.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _short_conv(x, p):
    t = x.shape[1]
    gate_in, gate_out, xs = jnp.split(x @ p["in_proj"], 3, axis=-1)
    taps = p["conv_kernel"]
    padded = jnp.pad(gate_in * xs, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + t] * taps[k] for k in range(taps.shape[0]))
    return (gate_out * conv) @ p["out_proj"]


def _rotate(x, theta):
    """``x``: ``[b, t, heads, d]``; pair ``i`` is ``(x[i], x[i + d/2])`` and
    turns by ``position * theta ** (-2 i / d)``."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def _attention(x, p, cfg):
    t, eps = x.shape[1], cfg["norm_eps"]
    theta = cfg["rope_parameters"]["rope_theta"]
    q, k, v = (jnp.einsum("btd,dhk->bthk", x, p[name]["kernel"])
               for name in ("query", "key", "value"))
    q = _rotate(_rms_norm(q, p["q_norm"], eps), theta)
    k = _rotate(_rms_norm(k, p["k_norm"], eps), theta)
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqs,bshk->bqhk", att, v)
    return jnp.einsum("bqhk,hkd->bqd", ctx, p["out"]["kernel"])


def _dense_mlp(x, p):
    return (jax.nn.silu(x @ p["w1"]["kernel"]) * (x @ p["w3"]["kernel"])
            ) @ p["w2"]["kernel"]


def _experts(x, p, bias, cfg):
    """``(out, (sel, counts))``: every expert held applied to every token,
    weighted by that token's weight for it (zero where it was not selected)."""
    lead, x = x.shape[:-1], x.reshape(-1, x.shape[-1])
    routed = p["router"].shape[1]
    scores = jax.nn.sigmoid(x @ p["router"])
    _, sel = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    picked = picked * cfg["routed_scaling_factor"]
    out = jnp.zeros_like(x)
    for i in range(p["w1"].shape[0]):
        mine = (sel == cfg["expert_offset"] + i).astype(x.dtype)    # 0/1
        y = (jax.nn.silu(x @ p["w1"][i]) * (x @ p["w3"][i])) @ p["w2"][i]
        out = out + (picked * mine).sum(-1, keepdims=True) * y
    counts = (sel[..., None] == jnp.arange(routed)).sum((0, 1))
    return out.reshape(lead + x.shape[-1:]), (sel, counts.astype(jnp.int32))


def _layer(p, h, bias, cfg):
    """``(h, routing)``; ``routing`` is ``None`` for a dense layer."""
    eps = cfg["norm_eps"]
    x = _rms_norm(h, p["operator_norm"]["scale"], eps)
    h = h + (_short_conv(x, p["conv"]) if "conv" in p
             else _attention(x, p["attention"], cfg))
    x = _rms_norm(h, p["ffn_norm"]["scale"], eps)
    if "mlp" in p:
        return h + _dense_mlp(x, p["mlp"]), None
    out, routing = _experts(x, p["experts"], bias, cfg)
    return h + out, routing


def _head(top, h, y, cfg):
    """Mean cross entropy from the last layer's output; ``top`` holds ``wte``
    and ``norm_f``."""
    h = _rms_norm(h, top["norm_f"]["scale"], cfg["norm_eps"])
    logp = jax.nn.log_softmax(h @ top["wte"].T, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()


def _layer_names(params):
    return sorted((name for name in params if name.startswith("layer_")),
                  key=lambda name: int(name.split("_")[1]))


def _bias(state, name, params):
    if state is not None and name in state:
        return jnp.asarray(state[name]["experts"]["selection_bias"], jnp.float32)
    if "experts" in params[name]:
        return jnp.zeros((params[name]["experts"]["router"].shape[1],),
                         jnp.float32)
    return None


def loss(params, x, y, cfg, state=None):
    """``(mean cross entropy, routing by expert layer)`` of next tokens ``y``
    given ``x`` (``[batch, seq]``)."""
    with jax.default_matmul_precision("highest"):
        h, routing = params["wte"][x], {}
        for name in _layer_names(params):
            h, routed = _layer(params[name], h, _bias(state, name, params), cfg)
            if routed is not None:
                routing[name] = routed
        return _head(params, h, y, cfg), routing


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


_READ = ("norm_eps", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "expert_offset")


def _hashable(cfg):
    """What the layers read of the configuration, as a static argument."""
    return tuple((k, cfg[k]) for k in _READ) + (
        ("rope_theta", cfg["rope_parameters"]["rope_theta"]),)


def _cfg(static):
    cfg = dict(static)
    cfg["rope_parameters"] = {"rope_theta": cfg.pop("rope_theta")}
    return cfg


def _routing(routing):
    return {name: {"selected": np.asarray(sel), "counts": np.asarray(counts)}
            for name, (sel, counts) in routing.items()}


@functools.partial(jax.jit, static_argnums=4)
def _whole(params, x, y, state, cfg):
    return jax.value_and_grad(loss, has_aux=True)(params, x, y, _cfg(cfg),
                                                  state)


def loss_and_grads(params, x, y, cfg, state=None):
    """``(loss, gradients, routing)``, the whole model differentiated at once."""
    # the batch is an argument: a closed-over array would be a constant of
    # the program, and every seed would compile anew
    (value, routing), grads = _whole(_f32(params), x, y, state, _hashable(cfg))
    return value, grads, _routing(routing)


@functools.partial(jax.jit, static_argnums=3)
def _layer_fwd(p, h, bias, cfg):
    with jax.default_matmul_precision("highest"):
        return _layer(p, h, bias, _cfg(cfg))


@functools.partial(jax.jit, static_argnums=4)
def _layer_bwd(p, h, bias, dh, cfg):
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda p_, h_: _layer(p_, h_, bias, _cfg(cfg))[0], p, h)
        return vjp(dh)


@functools.partial(jax.jit, static_argnums=3)
def _head_bwd(top, h, y, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda top_, h_: _head(top_, h_, y, _cfg(cfg)), argnums=(0, 1))(
                top, h)


@jax.jit
def _embed_bwd(wte, x, dh):
    return jax.vjp(lambda w: w[x], wte)[1](dh)[0]


def loss_and_grads_by_layer(params, x, y, cfg, state=None):
    """``(loss, gradients, routing)`` with the gradients as numpy arrays on
    the host, in the tree of ``params``.  Forward keeps every layer's input
    (the residual stream); backward walks the layers from the last, one
    program per layer kind."""
    cfg = _hashable(cfg)
    names = _layer_names(params)
    inputs, routing = [], {}
    h = jnp.asarray(params["wte"], jnp.float32)[x]
    for name in names:
        inputs.append(h)
        h, routed = _layer_fwd(_f32(params[name]), h,
                               _bias(state, name, params), cfg)
        if routed is not None:
            routing[name] = routed
    top = _f32({"wte": params["wte"], "norm_f": params["norm_f"]})
    value, (d_top, dh) = _head_bwd(top, h, y, cfg)
    grads = {"norm_f": jax.device_get(d_top["norm_f"])}
    for name in reversed(names):
        d_layer, dh = _layer_bwd(_f32(params[name]), inputs.pop(),
                                 _bias(state, name, params), dh, cfg)
        grads[name] = jax.device_get(d_layer)
    grads["wte"] = np.asarray(d_top["wte"] + _embed_bwd(top["wte"], x, dh))
    return value, {name: grads[name] for name in params}, _routing(routing)
