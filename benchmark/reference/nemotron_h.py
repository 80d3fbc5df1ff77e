"""Nemotron-H with latent experts (``nemotron_h``) in plain float32: every
layer is ``h = h + f(RMSNorm(h))`` with ``f`` one of a Mamba-2 mixer whose
recurrence runs **token by token** and whose gated norm is over each group's
channels, GQA softmax attention without positions, or a latent expert layer
as a **dense loop over the experts held** with a 0/1 mask of the reference's
own selection (no sort, no grouped matmul); a final RMSNorm, an untied head,
mean next-token cross entropy.

    s   = sigmoid(W_g u)                          # [routed experts]
    sel = top_k(s + b);  w = scale * s[sel] / (sum s[sel] + 1e-6)
    l   = W_down u                                # the latent space
    r   = sum_{e in sel, e held} w_e W2_e relu(W1_e l)^2
    y   = W_up r + V2 relu(V1 u)^2                # the shared expert on u

``cfg`` is the configuration file's own keys (``norm_eps``,
``num_experts_per_tok``, ``expert_offset``, ``mamba_head_dim``, ...).  A
layer's kind is read from its parameters; the router's width, the experts,
heads and groups held from their shapes.  With everything held this is the
uncut layer; with a share held, what the absent experts and heads would add
is left out, as in the program.  ``count_shared=False`` leaves the shared
expert out too (a share that is not the one to count it).  ``state`` is the
program's ``moe`` collection (the correction bias of every expert layer);
``None`` stands for zeros.

``forced`` (by expert layer, ``[tokens, k]``) makes the expert layers apply
a given selection in place of their own: the weights are still the
reference's own scores of those experts, and the selection reported is still
the reference's own.  A comparison under the other side's discrete choices
then reads the arithmetic, and the choices are compared apart.  ``select``
is the router alone, on a given normed hidden state.

``loss_and_grads`` differentiates the whole model at once (small sizes);
``loss_and_grads_by_layer`` does the same arithmetic one layer at a time and
hands every layer's gradient to the host before the next, so that the
published widths fit one chip beside nothing else.  Both also return the
reference's routing: per expert layer the selection ``[tokens, k]`` and the
rows sent to each of the router's experts.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _recurrence(x, dt, a, b, c, d):
    """One sequence.  ``x`` [T, H, P], ``dt`` [T, H], ``a`` [H], ``b``, ``c``
    [T, H, N] (each head reads its group's), ``d`` [H]; ``S_t = exp(dt_t a)
    S_{t-1} + dt_t x_t (x) b_t``, ``y_t = S_t c_t + d x_t``.  Nested in
    blocks whose inner scan is recomputed in the backward pass."""
    t, h, p = x.shape
    n = b.shape[-1]

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, (state * c_t[:, None, :]).sum(-1) + d[:, None] * x_t

    block = max(k for k in range(1, math.isqrt(t) + 1) if t % k == 0)
    inner = jax.checkpoint(lambda state, inputs: jax.lax.scan(
        step, state, inputs))
    blocks = jax.tree_util.tree_map(
        lambda v: v.reshape((t // block, block) + v.shape[1:]), (x, dt, b, c))
    _, y = jax.lax.scan(inner, jnp.zeros((h, p, n), x.dtype), blocks)
    return y.reshape(t, h, p)


def _mamba(x, p, cfg):
    """Heads, groups and the state's size from the shapes: ``A_log`` has one
    entry a head, the conv runs over ``heads x head_dim + 2 groups x state``
    channels."""
    hd, n = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    heads = p["A_log"].shape[0]
    d_inner, t = heads * hd, x.shape[1]
    groups = (p["conv_kernel"].shape[1] - d_inner) // (2 * n)
    z, xbc, dt = jnp.split(x @ p["in_proj"]["kernel"],
                           [d_inner, 2 * d_inner + 2 * groups * n], axis=-1)
    taps = p["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (taps.shape[0] - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, k:k + t] * taps[k]
                          for k in range(taps.shape[0])) + p["conv_bias"])
    xs, b, c = jnp.split(xbc, [d_inner, d_inner + groups * n], axis=-1)
    # head i reads group i // (heads / groups)
    by_head = lambda v: jnp.repeat(v.reshape(v.shape[:2] + (groups, n)),
                                   heads // groups, axis=2)
    y = jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0, None))(
        xs.reshape(xs.shape[:2] + (heads, hd)),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        by_head(b), by_head(c), p["D"])
    y = y.reshape(z.shape) * jax.nn.silu(z)
    # the gated norm over each group's channels
    grouped = y.reshape(y.shape[:2] + (groups, d_inner // groups))
    grouped = grouped * jax.lax.rsqrt(
        (grouped * grouped).mean(-1, keepdims=True) + cfg["norm_eps"])
    return (grouped.reshape(y.shape) * p["norm"]["scale"]) @ p[
        "out_proj"]["kernel"]


def _attention(x, p, cfg):
    t = x.shape[1]
    q, k, v = (jnp.einsum("btd,dhk->bthk", x, p[name]["kernel"])
               for name in ("query", "key", "value"))
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqs,bshk->bqhk", att, v)
    return jnp.einsum("bqhk,hkd->bqd", ctx, p["out"]["kernel"])


def _select(x, router, bias, cfg):
    """``(scores, sel)`` of tokens ``x``: ``[N, D]``."""
    scores = jax.nn.sigmoid(x @ router)
    return scores, jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])[1]


def _experts(x, p, bias, cfg, count_shared=True, forced=None):
    """``(out, (sel, counts))``: every expert held applied to every token's
    latent row, weighted by that token's weight for it (zero where it was not
    selected), brought back up, plus the shared expert on the hidden state.
    ``sel`` and ``counts`` are the reference's own whatever was applied."""
    lead, x = x.shape[:-1], x.reshape(-1, x.shape[-1])
    routed = p["router"].shape[1]
    scores, own = _select(x, p["router"], bias, cfg)
    sel = own if forced is None else forced
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    picked = picked * cfg["routed_scaling_factor"]
    latent = x @ p["latent_down"]
    mixed = jnp.zeros_like(latent)
    for i in range(p["w1"].shape[0]):
        mine = (sel == cfg["expert_offset"] + i).astype(x.dtype)    # 0/1
        y = _relu2(latent @ p["w1"][i]) @ p["w2"][i]
        mixed = mixed + (picked * mine).sum(-1, keepdims=True) * y
    out = mixed @ p["latent_up"]
    if count_shared:
        out = out + _relu2(x @ p["shared_w1"]) @ p["shared_w2"]
    counts = (own[..., None] == jnp.arange(routed)).sum((0, 1))
    return out.reshape(lead + x.shape[-1:]), (own, counts.astype(jnp.int32))


def layer(p, h, bias, cfg, count_shared=True, forced=None):
    """``(h + f(RMSNorm(h)), routing)``; ``routing`` is ``None`` for a mixer."""
    x = _rms_norm(h, p["norm"]["scale"], cfg["norm_eps"])
    if "mamba" in p:
        return h + _mamba(x, p["mamba"], cfg), None
    if "attention" in p:
        return h + _attention(x, p["attention"], cfg), None
    out, routing = _experts(x, p["experts"], bias, cfg, count_shared, forced)
    return h + out, routing


def _head(top, h, y, cfg):
    """Mean cross entropy from the last layer's output; ``top`` holds
    ``norm_f`` and the untied ``head`` ``[vocabulary, hidden]``."""
    h = _rms_norm(h, top["norm_f"]["scale"], cfg["norm_eps"])
    logp = jax.nn.log_softmax(h @ top["head"].T, axis=-1)
    return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()


def _layer_names(params):
    return sorted((name for name in params if name.startswith("layer_")),
                  key=lambda name: int(name.split("_")[1]))


def _bias(state, name, params):
    if "experts" not in params[name]:
        return None
    if state is not None and name in state:
        return jnp.asarray(state[name]["experts"]["correction_bias"],
                           jnp.float32)
    return jnp.zeros((params[name]["experts"]["router"].shape[1],), jnp.float32)


def _forced(forced, name):
    return None if forced is None else jnp.asarray(forced[name], jnp.int32)


def loss(params, x, y, cfg, state=None, forced=None):
    """``(mean cross entropy, routing by expert layer)`` of next tokens ``y``
    given ``x`` (``[batch, seq]``)."""
    with jax.default_matmul_precision("highest"):
        h, routing = params["wte"][x], {}
        for name in _layer_names(params):
            h, routed = layer(
                params[name], h, _bias(state, name, params), cfg,
                forced=_forced(forced, name) if "experts" in params[name]
                else None)
            if routed is not None:
                routing[name] = routed
        return _head(params, h, y, cfg), routing


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


_READ = ("norm_eps", "num_experts_per_tok", "norm_topk_prob",
         "routed_scaling_factor", "expert_offset", "mamba_head_dim",
         "ssm_state_size")


def _hashable(cfg):
    """What the layers read of the configuration, as a static argument."""
    return tuple((k, cfg[k]) for k in _READ)


def _routing(routing):
    return {name: {"selected": np.asarray(sel), "counts": np.asarray(counts)}
            for name, (sel, counts) in routing.items()}


@functools.partial(jax.jit, static_argnums=5)
def _whole(params, x, y, state, forced, cfg):
    return jax.value_and_grad(loss, has_aux=True)(params, x, y, dict(cfg),
                                                  state, forced)


def loss_and_grads(params, x, y, cfg, state=None, forced=None):
    """``(loss, gradients, routing)``, the whole model differentiated at once."""
    # the batch is an argument: a closed-over array would be a constant of
    # the program, and every seed would compile anew
    (value, routing), grads = _whole(_f32(params), x, y, state, forced,
                                     _hashable(cfg))
    return value, grads, _routing(routing)


@functools.partial(jax.jit, static_argnums=3)
def _select_jit(u, router, bias, cfg):
    with jax.default_matmul_precision("highest"):
        return _select(u, router, bias, dict(cfg))[1]


def select(u, router, bias, cfg):
    """The reference's selection ``[tokens, k]`` for the normed hidden states
    ``u`` (``[..., hidden]``, any float dtype) that a router was given."""
    u = jnp.asarray(u, jnp.float32)
    return np.asarray(_select_jit(
        u.reshape(-1, u.shape[-1]), jnp.asarray(router, jnp.float32),
        jnp.asarray(bias, jnp.float32), _hashable(cfg)))


@functools.partial(jax.jit, static_argnums=4)
def _layer_fwd(p, h, bias, forced, cfg):
    with jax.default_matmul_precision("highest"):
        return layer(p, h, bias, dict(cfg), forced=forced)


@functools.partial(jax.jit, static_argnums=5)
def _layer_bwd(p, h, bias, forced, dh, cfg):
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(lambda p_, h_: layer(p_, h_, bias, dict(cfg),
                                              forced=forced)[0], p, h)
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


def loss_and_grads_by_layer(params, x, y, cfg, state=None, forced=None):
    """``(loss, gradients, routing)`` with the gradients as numpy arrays on
    the host, in the tree of ``params``.  Forward keeps every layer's input
    (the residual stream); backward walks the layers from the last, one
    program per layer kind."""
    cfg = _hashable(cfg)
    names = _layer_names(params)
    inputs, routing = [], {}
    applied = lambda name: (_forced(forced, name)
                            if "experts" in params[name] else None)
    h = jnp.asarray(params["wte"], jnp.float32)[x]
    for name in names:
        inputs.append(h)
        h, routed = _layer_fwd(_f32(params[name]), h,
                               _bias(state, name, params), applied(name), cfg)
        if routed is not None:
            routing[name] = routed
    top = _f32({"head": params["head"], "norm_f": params["norm_f"]})
    value, (d_top, dh) = _head_bwd(top, h, y, cfg)
    grads = {"norm_f": jax.device_get(d_top["norm_f"]),
             "head": jax.device_get(d_top["head"])}
    for name in reversed(names):
        d_layer, dh = _layer_bwd(_f32(params[name]), inputs.pop(),
                                 _bias(state, name, params), applied(name),
                                 dh, cfg)
        grads[name] = jax.device_get(d_layer)
    grads["wte"] = np.asarray(_embed_bwd(
        jnp.asarray(params["wte"], jnp.float32), x, dh))
    return value, {name: grads[name] for name in params}, _routing(routing)
