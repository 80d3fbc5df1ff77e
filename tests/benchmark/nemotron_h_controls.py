"""The upper readings of ``nemotron3_super_120b_o2``'s tolerance: the cell's
own comparison run on systems that have to come out as not correct.  For the
chip, at the cell's size (not a pytest file)::

    python tests/benchmark/nemotron_h_controls.py --seed 2147484001 \
        --variants base,bf16_parts,drop_expert

Every variant goes through the family's ``build()``, ``first_dispatch()`` and
``check()`` as ``run.py`` drives them, so what is read is the timed
executable's step; the reference's gradient is computed anew for every
variant, under that variant's selection.  One ``VERDICT`` line per variant,
then every kind of leaf's worst error against its own norm.

``bf16_router``: the router's scores and weights computed in bf16 (the
product, the sigmoid, the normalisation).  ``bf16_decay``: the scan's float32
parts (dt, A, running sums, decays, accumulators) in bf16, as
``granite_hybrid_controls.py`` plants them.  ``bf16_parts``: every float32
part of the model in bf16, the nearest precision below the configuration's:
the router and the scan as before, and the statistics of every RMSNorm.
**Every rounding is an operation of its own** (``lax.reduce_precision``): a
pair of converts, float32 to bf16 and back, is taken out by the TPU's
compiler, which keeps the excess precision, and a control written with
``astype`` read the sound system's very digits on the chip (PR 33's first
controls; ``PERF.md`` section 6).
``unnormalised``: the weights of a token's experts not divided by their sum.
``no_skip``: the term ``D x`` left out of every mixer.
``drop_expert``: the pairs of one held expert left out of the result.
``half_batch``: the step trains on the first half of the batch twice.
``--tiny 1`` cuts the widths for a CPU rehearsal; the tests in
``test_benchmark_nemotron_h.py`` plant the same faults through
:func:`degrade`.
"""

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import flax.linen as nn  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from apex_tpu.models import granite_hybrid, nemotron_h  # noqa: E402
from apex_tpu.ops import moe, ssd  # noqa: E402
from benchmark import compare, run  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from lfm2_moe_controls import (first_half_twice,  # noqa: E402, F401
                               verdict_with_leaves)

CELL = "nemotron3_super_120b_o2.b2_seq8192"
TINY = dict(vocab_size=512, hidden_size=64, mamba_num_heads=4,
            mamba_head_dim=16, ssm_state_size=16, chunk_size=16,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            moe_latent_size=32, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=96, num_experts_per_tok=4,
            n_routed_experts=2, expert_offset=2)
TINY_ROUTED = 16        # the tiny router's width
DROPPED = 3             # drop_expert: this expert of the router's (held in
#                         the cell, experts 0 to 7, and in the tiny cut, 2 and 3)

REAL = {"route": moe.route, "ssd": granite_hybrid.ssd_chunked,
        "norm": nemotron_h.RMSNorm}


def _bf16(v):
    """``v`` rounded to bf16's 8 exponent and 7 mantissa bits, in its own
    dtype, by an operation the compiler keeps."""
    return jax.lax.reduce_precision(v, 8, 7)


def _bf16_route(x, w_gate, bias, *, top_k, norm_topk_prob=True, scaling=1.0):
    scores = _bf16(jax.nn.sigmoid(_bf16(jnp.dot(
        _bf16(x.astype(jnp.float32)), _bf16(w_gate)))))
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(scores) + bias, top_k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if norm_topk_prob:
        weights = _bf16(weights / _bf16(weights.sum(-1, keepdims=True) + 1e-6))
    experts = jnp.arange(w_gate.shape[1], dtype=sel.dtype)
    counts = (sel[..., None] == experts).sum((0, 1), dtype=jnp.int32)
    return sel, _bf16(weights * scaling), counts


def _route_without(expert):
    def route(*a, **kw):
        sel, weights, counts = REAL["route"](*a, **kw)
        return sel, jnp.where(sel == expert, 0, weights), counts
    return route


def _bf16_scan():
    """``ops.ssd.ssd_chunked`` with bf16 where it says float32."""
    source = open(ssd.__file__, encoding="utf-8").read()
    marked = "f32, cdt = jnp.float32, x.dtype"
    assert marked in source
    scope = {"__name__": "ssd_bf16"}
    exec(compile(source.replace(marked, "f32, cdt = jnp.bfloat16, x.dtype"),
                 "ssd_bf16", "exec"), scope)
    chunked = scope["ssd_chunked"]
    return lambda x, dt, a, *rest: chunked(x, _bf16(dt), _bf16(a), *rest)


class _Bf16RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = x.astype(jnp.float32)
        inv = _bf16(jax.lax.rsqrt(
            _bf16(jnp.mean(_bf16(y * y), axis=-1, keepdims=True)) + self.eps))
        return _bf16(_bf16(y * inv) * _bf16(scale)).astype(x.dtype)


def degrade(variant, config):
    """Plants the variant in the program and returns the configuration the
    family builds from."""
    moe.route, granite_hybrid.ssd_chunked = REAL["route"], REAL["ssd"]
    nemotron_h.RMSNorm = REAL["norm"]
    if variant == "bf16_router":
        moe.route = _bf16_route
    elif variant == "bf16_decay":
        granite_hybrid.ssd_chunked = _bf16_scan()
    elif variant == "bf16_parts":
        moe.route, granite_hybrid.ssd_chunked = _bf16_route, _bf16_scan()
        nemotron_h.RMSNorm = _Bf16RMSNorm
    elif variant == "drop_expert":
        moe.route = _route_without(DROPPED)
    elif variant == "no_skip":
        granite_hybrid.ssd_chunked = lambda x, dt, a, b, c, d: REAL["ssd"](
            x, dt, a, b, c, None)
    elif variant == "unnormalised":
        return dict(config, norm_topk_prob=False)
    elif variant not in ("base", "half_batch"):
        raise SystemExit(f"unknown variant {variant!r}")
    return config


def tiny(config, traffic):
    """The cell's configuration and traffic at CPU widths: two of sixteen
    experts held, from the third; the eleven letters as they are."""
    config = dict(config, **TINY)
    config["published"] = dict(config["published"],
                               n_routed_experts=TINY_ROUTED)
    return config, dict(traffic, seq=64)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=2147484001)
    p.add_argument("--variants", default="base,bf16_parts,drop_expert")
    p.add_argument("--tiny", type=int, default=0)
    a = p.parse_args()
    from apex_tpu import cache

    print(f"compile cache: {cache.enable()}", flush=True)
    plan = run.resolve(CELL)
    config, traffic = plan.config, plan.traffic
    if a.tiny:
        config, traffic = tiny(config, traffic)

    reference_mean = plan.family.reference_mean

    def timed(p0, x, y, rows, cfg, model_state, forced=None):
        # of the configuration as it is, under the variant's selection
        t = time.time()
        mean = reference_mean(p0, x, y, rows, config, model_state, forced)
        print(f"reference: loss {mean[0]:.6f} ({time.time() - t:.1f} s)",
              flush=True)
        return mean

    plan.family.reference_mean = timed
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
