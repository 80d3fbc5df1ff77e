"""granite-4.0-h (``granitemoehybrid`` without routed experts) in plain
float32: Mamba-2 layers with the recurrence run **token by token**, GQA
softmax attention layers without positions, a shared SwiGLU MLP after every
mixer, RMSNorm, the published multipliers, tied head, mean next-token cross
entropy.

``cfg`` is the configuration file's own keys (``mamba_n_heads``,
``residual_multiplier``, ...).  A layer's kind is read from its parameters.
The recurrence is nested in blocks whose inner scan is recomputed in the
backward pass, which is all the recomputation there is: 4,096 states of
2 MB a sequence would not fit otherwise.

``loss_and_grads`` differentiates the whole model at once (small sizes);
``loss_and_grads_by_layer`` does the same arithmetic one layer at a time and
hands every layer's gradient to the host before the next, so that the
published widths fit one chip beside nothing else.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _recurrence(x, dt, a, b, c, d):
    """One sequence.  ``x`` [T, H, P], ``dt`` [T, H], ``a`` [H], ``b``, ``c``
    [T, N] (one group), ``d`` [H]; ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x)
    b_t``, ``y_t = S_t c_t + d x_t``."""
    t, h, p = x.shape
    n = b.shape[-1]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return state, (state * c_t).sum(-1) + d[:, None] * x_t

    block = max(k for k in range(1, math.isqrt(t) + 1) if t % k == 0)
    inner = jax.checkpoint(lambda state, inputs: jax.lax.scan(
        step, state, inputs))
    blocks = jax.tree_util.tree_map(
        lambda v: v.reshape((t // block, block) + v.shape[1:]), (x, dt, b, c))
    _, y = jax.lax.scan(inner, jnp.zeros((h, p, n), x.dtype), blocks)
    return y.reshape(t, h, p)


def _mamba(x, p, cfg):
    heads, hd, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                    cfg["mamba_d_state"])
    if cfg["mamba_n_groups"] != 1:
        raise NotImplementedError("the reference is written for one group")
    d_inner, t = heads * hd, x.shape[1]
    z, xbc, dt = jnp.split(x @ p["in_proj"]["kernel"],
                           [d_inner, 2 * d_inner + 2 * n], axis=-1)
    taps = p["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, k:k + t] * taps[k]
                          for k in range(taps.shape[0])) + p["conv_bias"])
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    y = jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0, None))(
        xs.reshape(xs.shape[:2] + (heads, hd)),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]), b, c, p["D"])
    y = y.reshape(z.shape) * jax.nn.silu(z)
    return _rms_norm(y, p["norm"]["scale"], cfg["rms_norm_eps"]) @ p[
        "out_proj"]["kernel"]


def _attention(x, p, cfg):
    t = x.shape[1]
    q, k, v = (jnp.einsum("btd,dhk->bthk", x, p[name]["kernel"])
               for name in ("query", "key", "value"))
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqs,bshk->bqhk", att, v)
    return jnp.einsum("bqhk,hkd->bqd", ctx, p["out"]["kernel"])


def _layer(p, h, cfg):
    eps, m_r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = _rms_norm(h, p["norm1"]["scale"], eps)
    mixed = _mamba(x, p["mamba"], cfg) if "mamba" in p else _attention(
        x, p["attention"], cfg)
    h = h + m_r * mixed
    gate, up = jnp.split(_rms_norm(h, p["norm2"]["scale"], eps)
                         @ p["mlp_in"]["kernel"], 2, axis=-1)
    return h + m_r * ((jax.nn.silu(gate) * up) @ p["mlp_out"]["kernel"])


def _embed(wte, x, cfg):
    return wte[x] * cfg["embedding_multiplier"]


def _head(top, h, y, cfg):
    """Mean cross entropy from the last layer's output; ``top`` holds ``wte``
    and ``norm_f``."""
    h = _rms_norm(h, top["norm_f"]["scale"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(h @ top["wte"].T / cfg["logits_scaling"], axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()


def _layer_names(params):
    return sorted((name for name in params if name.startswith("layer_")),
                  key=lambda name: int(name.split("_")[1]))


def loss(params, x, y, cfg):
    """Mean cross entropy of next tokens ``y`` given ``x`` (``[batch, seq]``)."""
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], x, cfg)
        for name in _layer_names(params):
            h = _layer(params[name], h, cfg)
        return _head(params, h, y, cfg)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=3)
def _whole(params, x, y, cfg):
    return jax.value_and_grad(loss)(params, x, y, dict(cfg))


def loss_and_grads(params, x, y, cfg):
    # the batch is an argument: a closed-over array would be a constant of
    # the program, and every seed would compile anew
    return _whole(_f32(params), x, y, _hashable(cfg))


@functools.partial(jax.jit, static_argnums=2)
def _layer_fwd(p, h, cfg):
    with jax.default_matmul_precision("highest"):
        return _layer(p, h, dict(cfg))


@functools.partial(jax.jit, static_argnums=3)
def _layer_bwd(p, h, dh, cfg):
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda p_, h_: _layer(p_, h_, dict(cfg)), p, h)
        return vjp(dh)


@functools.partial(jax.jit, static_argnums=3)
def _head_bwd(top, h, y, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda top_, h_: _head(top_, h_, y, dict(cfg)), argnums=(0, 1))(
                top, h)


@functools.partial(jax.jit, static_argnums=3)
def _embed_bwd(wte, x, dh, cfg):
    return jax.vjp(lambda w: _embed(w, x, dict(cfg)), wte)[1](dh)[0]


def loss_and_grads_by_layer(params, x, y, cfg):
    """``(loss, gradients)`` with the gradients as numpy arrays on the host,
    in the tree of ``params``.  Forward keeps every layer's input (the
    residual stream, 32 MB a layer at the published sizes); backward walks
    the layers from the last, one program per layer kind."""
    cfg = _hashable(cfg)
    names = _layer_names(params)
    inputs, h = [], _embed(jnp.asarray(params["wte"], jnp.float32), x,
                           dict(cfg))
    for name in names:
        inputs.append(h)
        h = _layer_fwd(_f32(params[name]), h, cfg)
    top = _f32({"wte": params["wte"], "norm_f": params["norm_f"]})
    value, (d_top, dh) = _head_bwd(top, h, y, cfg)
    grads = {"norm_f": jax.device_get(d_top["norm_f"])}
    for name in reversed(names):
        d_layer, dh = _layer_bwd(_f32(params[name]), inputs.pop(), dh, cfg)
        grads[name] = jax.device_get(d_layer)
    grads["wte"] = np.asarray(d_top["wte"] + _embed_bwd(top["wte"], x, dh, cfg))
    return value, {name: grads[name] for name in params}
