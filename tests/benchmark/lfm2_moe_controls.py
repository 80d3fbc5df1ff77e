"""The upper readings of ``lfm2_24b_a2b_o2``'s tolerance: the cell's own
comparison run on systems that have to come out as not correct.  For the
chip, at the cell's size (not a pytest file)::

    python tests/benchmark/lfm2_moe_controls.py --seed 2147484001 \
        --variants base,bf16_router,unnormalised

Every variant goes through the family's ``build()``, ``first_dispatch()`` and
``check()`` as ``run.py`` drives them, so what is read is the timed
executable's step; the reference's gradient and routing are computed once and
kept on the host.  One ``VERDICT`` line per variant, then every kind of leaf's
worst error against its own norm.

``bf16_router``: the router's scores and weights computed in bf16 (the
product, the sigmoid, the normalisation).  ``bf16_parts``: every float32 part
of the model in bf16, the nearest precision below the configuration's: the
router as before, the rotary angles, their sines and cosines and the
rotation, and the statistics of every RMSNorm.  ``unnormalised``: the weights
of a token's four experts not divided by their sum.  ``no_rope``: the rotation of q and k left out (the
per-head norm stays).  ``drop_expert``: the rows of one held expert left out
of the result.  ``half_batch``: the step trains on the first half of the
batch twice.  ``--tiny 1`` cuts the widths for a CPU rehearsal; the tests in
``test_benchmark_lfm2_moe.py`` plant the same faults through :func:`degrade`.
"""

import argparse
import collections
import json
import os
import re
import sys
import time
import traceback
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from apex_tpu.models import lfm2_moe  # noqa: E402
from apex_tpu.normalization.rms_norm import rms_norm  # noqa: E402
from apex_tpu.ops import moe  # noqa: E402
from benchmark import compare, run  # noqa: E402

CELL = "lfm2_24b_a2b_o2.b4_seq4096"
TINY = dict(vocab_size=512, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=160,
            moe_intermediate_size=32, num_experts=2, expert_offset=2)
TINY_ROUTED = 8         # the tiny router's width
DROPPED = 3             # drop_expert: this expert of the router's (held in
#                         the cell, experts 0 to 15, and in the tiny cut, 2 and 3)

REAL = {"route": moe.route, "qk_norm_rope": lfm2_moe.qk_norm_rope,
        "norm": lfm2_moe.RMSNorm, "verdict": compare.verdict}


def _bf16_route(x, w_gate, bias, *, top_k, norm_topk_prob=True, scaling=1.0):
    bf16 = jnp.bfloat16
    scores = jax.nn.sigmoid(jnp.dot(x.astype(bf16), w_gate.astype(bf16)))
    _, sel = jax.lax.top_k(
        jax.lax.stop_gradient(scores).astype(jnp.float32) + bias, top_k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + bf16(1e-6))
    experts = jnp.arange(w_gate.shape[1], dtype=sel.dtype)
    counts = (sel[..., None] == experts).sum((0, 1), dtype=jnp.int32)
    return sel, (weights * bf16(scaling)).astype(jnp.float32), counts


def _route_without(expert):
    def route(*a, **kw):
        sel, weights, counts = REAL["route"](*a, **kw)
        return sel, jnp.where(sel == expert, 0, weights), counts
    return route


def _norm_without_rope(q, k, q_weight, k_weight, positions=None, *,
                       theta=10000.0, eps=1e-5):
    return rms_norm(q, q_weight, eps), rms_norm(k, k_weight, eps)


def _bf16_norm(x, weight, eps):
    bf16 = jnp.bfloat16
    x = x.astype(bf16)
    inv = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + bf16(eps))
    return x * inv * weight.astype(bf16)


class _Bf16RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return _bf16_norm(x, scale, self.eps).astype(x.dtype)


def _bf16_norm_rope(q, k, q_weight, k_weight, positions=None, *,
                    theta=10000.0, eps=1e-5):
    bf16, d = jnp.bfloat16, q.shape[-1]
    freq = jnp.exp(jnp.arange(d // 2, dtype=bf16) * bf16(-2.0 / d)
                   * jnp.log(bf16(theta)))
    angle = (jnp.arange(q.shape[1]).astype(bf16)[:, None, None] * freq)
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def turn(x, w):
        x1, x2 = jnp.split(_bf16_norm(x, w, eps), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)
    return turn(q, q_weight), turn(k, k_weight)


def degrade(variant, config):
    """Plants the variant in the program and returns the configuration the
    family builds from."""
    moe.route, lfm2_moe.qk_norm_rope = REAL["route"], REAL["qk_norm_rope"]
    lfm2_moe.RMSNorm = REAL["norm"]
    if variant == "bf16_router":
        moe.route = _bf16_route
    elif variant == "bf16_parts":
        moe.route, lfm2_moe.qk_norm_rope = _bf16_route, _bf16_norm_rope
        lfm2_moe.RMSNorm = _Bf16RMSNorm
    elif variant == "drop_expert":
        moe.route = _route_without(DROPPED)
    elif variant == "no_rope":
        lfm2_moe.qk_norm_rope = _norm_without_rope
    elif variant == "unnormalised":
        return dict(config, norm_topk_prob=False)
    elif variant not in ("base", "half_batch"):
        raise SystemExit(f"unknown variant {variant!r}")
    return config


def first_half_twice(pipe):
    def step_window(state, window, k):
        half = lambda a: jnp.concatenate(
            [a[:, :max(1, a.shape[1] // 2)]] * 2, axis=1)[:, :a.shape[1]]
        return pipe.step_window(
            state, jax.tree_util.tree_map(half, window), k)
    return types.SimpleNamespace(step_window=step_window)


def verdict_with_leaves(sys_loss, ref_loss, sys_grads, ref_grads, tol,
                        noise=None):
    """``compare.verdict`` and, printed, every kind of leaf's worst error
    against its own norm with its share of the whole gradient's norm."""
    def leaves(tree):                   # one float64 leaf at a time
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            yield jax.tree_util.keystr(path), np.asarray(leaf, np.float64).ravel()

    whole = np.sqrt(sum(float(r @ r) for _, r in leaves(ref_grads)))
    kinds = collections.defaultdict(lambda: (0.0, None, 0.0))
    for (name, s), (_, r) in zip(leaves(sys_grads), leaves(ref_grads)):
        own = np.sqrt(float(r @ r))
        err = np.sqrt(float((s - r) @ (s - r))) / max(own, 1e-300)
        kind = re.sub(r"\['layer_\d+'\]", "", name)
        if err >= kinds[kind][0]:
            kinds[kind] = (err, name, own / whole)
    print("  by kind (worst leaf's error over its own norm, its share of the "
          "whole norm): " + "; ".join(
              f"{kind} {err:.4f} {share:.2e}"
              for kind, (err, _, share) in sorted(kinds.items())), flush=True)
    return REAL["verdict"](sys_loss, ref_loss, sys_grads, ref_grads, tol, noise)


def tiny(config, traffic):
    """The cell's configuration and traffic at CPU widths: two of eight
    experts held, from the third."""
    config = dict(config, **TINY)
    config["published"] = dict(config["published"], num_experts=TINY_ROUTED)
    return config, dict(traffic, seq=64)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=2147484001)
    p.add_argument("--variants", default="base,bf16_router,unnormalised")
    p.add_argument("--tiny", type=int, default=0)
    a = p.parse_args()
    from apex_tpu import cache

    print(f"compile cache: {cache.enable()}", flush=True)
    plan = run.resolve(CELL)
    config, traffic = plan.config, plan.traffic
    if a.tiny:
        config, traffic = tiny(config, traffic)

    kept, reference_mean = {}, plan.family.reference_mean

    def once(p0, x, y, rows, cfg, model_state):  # of the configuration as it is
        if "mean" not in kept:
            t = time.time()
            kept["mean"] = reference_mean(p0, x, y, rows, config, model_state)
            print(f"reference: loss {kept['mean'][0]:.6f} "
                  f"({time.time() - t:.1f} s)", flush=True)
        return kept["mean"]

    plan.family.reference_mean = once
    compare.verdict = verdict_with_leaves
    for variant in a.variants.split(","):
        t = time.time()
        try:
            cell = plan.family.build(degrade(variant, config), traffic,
                                     jax.devices()[:1], a.seed)
            cell.pipe.warmup(cell.state, cell.window)
            cell.first_dispatch()
            if variant == "half_batch":
                cell.pipe = first_half_twice(cell.pipe)
            print(f"VERDICT {variant} seed {a.seed}: "
                  + json.dumps(cell.check()) + f" ({time.time() - t:.1f} s)",
                  flush=True)
            jax.tree_util.tree_map(lambda x: x.delete(), cell.state.params)
            del cell
        except Exception:
            print(f"VERDICT {variant} seed {a.seed}: raised", flush=True)
            traceback.print_exc()


if __name__ == "__main__":
    main()
