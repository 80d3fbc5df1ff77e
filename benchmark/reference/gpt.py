"""GPT-2 (Radford et al. 2019) in plain float32: pre-LN blocks, learned
positions, tanh-GELU (``gelu_new``), causal softmax attention, tied head,
mean next-token cross entropy.

Departure from the published configuration: no dropout (``attn_pdrop``,
``embd_pdrop``, ``resid_pdrop`` are 0.1 there); the program's model has none.
"""

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _attention(x, p):
    t = x.shape[1]
    proj = lambda name: (jnp.einsum("btd,dhk->bthk", x, p[name]["kernel"])
                         + p[name]["bias"])
    q, k, v = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqs,bshk->bqhk", att, v)
    return (jnp.einsum("bqhk,hkd->bqd", ctx, p["out"]["kernel"])
            + p["out"]["bias"])


def loss(params, x, y, *, eps=1e-5):
    """Mean cross entropy of next tokens ``y`` given ``x`` (``[batch, seq]``)."""
    with jax.default_matmul_precision("highest"):
        h = params["wte"][x] + params["wpe"][: x.shape[1]]
        n_layers = sum(name.startswith("block_") for name in params)
        for i in range(n_layers):
            p = params[f"block_{i}"]
            h = h + _attention(_layer_norm(h, p["ln1"], eps), p["attention"])
            m = _layer_norm(h, p["ln2"], eps)
            m = m @ p["mlp_up"]["kernel"] + p["mlp_up"]["bias"]
            m = jax.nn.gelu(m, approximate=True)
            h = h + m @ p["mlp_down"]["kernel"] + p["mlp_down"]["bias"]
        logits = _layer_norm(h, params["ln_f"], eps) @ params["wte"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()


def loss_and_grads(params, x, y, *, eps=1e-5):
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                    params)
    # the batch is an argument: a closed-over array would be a constant of
    # the program, and every seed would compile anew
    return jax.jit(jax.value_and_grad(
        lambda p, x_, y_: loss(p, x_, y_, eps=eps)))(params, x, y)
