"""The upper readings of ``smallthinker_21b_a3b_o2``'s tolerance, and the
cell's steadiness over seeds.  For the chip, at the cell's size (not a
pytest file)::

    python tests/benchmark/smallthinker_controls.py --seed 2147484001 \
        --variants base,bf16_parts,bf16_router
    python tests/benchmark/smallthinker_controls.py --steady 1 \
        --seeds 2147484001,3000000017 --weights-seed -1

Every variant goes through the family's ``build()``, ``first_dispatch()`` and
``check()`` as ``run.py`` drives them, so what is read is the timed
executable's step; the reference's gradient is computed anew for every
variant, under that variant's selection, and always of the configuration as
it is.  One ``VERDICT`` line per variant, then every kind of leaf's worst
error against its own norm.

The faults: ``sigmoid``, the router's weights the normalised sigmoid of the
selected logits in place of their softmax; ``silu``, SiLU in place of the
experts' ReLU; ``rope_on_nope``, rotary positions on the global layer too;
``no_window``, every layer global; ``router_n2``, the router reading the
experts' input (the normed sum after attention) in place of the attention's;
``bf16_router``, the router's logits and weights rounded to bf16;
``bf16_parts``, every float32 part of the model in bf16, the nearest precision
below the configuration's: the router as before, the statistics of every
RMSNorm and the rotary angles with their sines and cosines.  **Every rounding
is an operation of its own** (``lax.reduce_precision``): the TPU's compiler
takes a pair of converts out (``PERF.md``, PR 33).  ``half_sequence``: the
step trains on the first half of the sequence twice.

``--steady 1`` times the window as ``run.py`` does (the median dispatch
interval, ``--seconds`` long) for each of ``--seeds``, without the check, and
prints the rows held at the window's first and last step and the load's
max/mean; ``--weights-seed n`` draws the weights from the key ``n`` in place
of the recipe's ``weights_key``, ``--lr`` sets AdamW's rate.

``--tiny 1`` cuts the widths for a CPU rehearsal; the tests in
``test_benchmark_smallthinker.py`` plant the same faults through
:func:`degrade`.
"""

import argparse
import json
import os
import statistics
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

from apex_tpu.models import smallthinker  # noqa: E402
from apex_tpu.ops import moe  # noqa: E402
from benchmark import compare, run, smallthinker_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from lfm2_moe_controls import verdict_with_leaves  # noqa: E402

CELL = "smallthinker_21b_a3b_o2.b1_seq16384"
TINY = dict(vocab_size=512, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window_size=16,
            moe_ffn_hidden_size=32, moe_num_active_primary_experts=4,
            moe_num_primary_experts=4, expert_offset=4)
TINY_ROUTED = 16        # the tiny router's width

REAL = {"route": moe.route, "relu": moe.ACTIVATIONS["relu"],
        "norm": smallthinker.RMSNorm, "rope": smallthinker.apply_rope,
        "moe_layer": smallthinker.moe_layer}


def _bf16(v):
    """``v`` rounded to bf16's 8 exponent and 7 mantissa bits, in its own
    dtype, by an operation the compiler keeps."""
    return jax.lax.reduce_precision(v, 8, 7)


def _bf16_route(x, w_gate, bias, *, top_k, norm_topk_prob=True, scaling=1.0,
                score="softmax"):
    logits = _bf16(jnp.dot(_bf16(x.astype(jnp.float32)), _bf16(w_gate)))
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(logits), top_k)
    weights = _bf16(jax.nn.softmax(_bf16(jnp.take_along_axis(
        logits, sel, axis=-1)), axis=-1))
    experts = jnp.arange(w_gate.shape[1], dtype=sel.dtype)
    counts = (sel[..., None] == experts).sum((0, 1), dtype=jnp.int32)
    return sel, weights * scaling, counts


def _sigmoid_route(x, w_gate, bias, *, top_k, norm_topk_prob=True,
                   scaling=1.0, score="softmax"):
    return REAL["route"](x, w_gate, jnp.zeros((w_gate.shape[1],), jnp.float32),
                         top_k=top_k, scaling=scaling)


def _router_reads_x(x, *args, router_in=None, **kw):
    return REAL["moe_layer"](x, *args, **kw)


def _bf16_rope(x, positions=None, *, theta=10000.0):
    d = x.shape[-1]
    freq = _bf16(jnp.exp(_bf16(jnp.arange(d // 2, dtype=jnp.float32)
                               * (-2.0 / d) * jnp.log(jnp.float32(theta)))))
    angle = _bf16(_bf16(jnp.arange(x.shape[1], dtype=jnp.float32))[:, None]
                  * freq)[:, None]
    cos, sin = _bf16(jnp.cos(angle)), _bf16(jnp.sin(angle))
    x1, x2 = jnp.split(_bf16(x.astype(jnp.float32)), 2, axis=-1)
    return jnp.concatenate([_bf16(x1 * cos - x2 * sin),
                            _bf16(x2 * cos + x1 * sin)], -1).astype(x.dtype)


class _Bf16RMSNorm(nn.Module):
    eps: float = 1e-6

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
    family builds from (the reference is given the configuration as it is)."""
    moe.route, moe.ACTIVATIONS["relu"] = REAL["route"], REAL["relu"]
    smallthinker.RMSNorm, smallthinker.apply_rope = REAL["norm"], REAL["rope"]
    smallthinker.moe_layer = REAL["moe_layer"]
    layers = config["num_hidden_layers"]
    if variant == "sigmoid":
        moe.route = _sigmoid_route
    elif variant == "silu":
        moe.ACTIVATIONS["relu"] = jax.nn.silu
    elif variant == "rope_on_nope":
        return dict(config, rope_layout=[1] * layers)
    elif variant == "no_window":
        return dict(config, sliding_window_layout=[0] * layers)
    elif variant == "router_n2":
        smallthinker.moe_layer = _router_reads_x
    elif variant == "bf16_router":
        moe.route = _bf16_route
    elif variant == "bf16_parts":
        moe.route = _bf16_route
        smallthinker.RMSNorm = _Bf16RMSNorm
        smallthinker.apply_rope = _bf16_rope
    elif variant not in ("base", "half_sequence"):
        raise SystemExit(f"unknown variant {variant!r}")
    return config


def first_half_twice(pipe):
    """The pipeline training on the first half of every sequence twice."""
    def step_window(state, window, k):
        half = lambda a: jnp.concatenate([a[..., :a.shape[-1] // 2]] * 2,
                                         axis=-1)
        return pipe.step_window(state, jax.tree_util.tree_map(half, window), k)
    return types.SimpleNamespace(step_window=step_window)


def tiny(config, traffic):
    """The cell's configuration and traffic at CPU widths: four of sixteen
    experts held, from the fifth; one period of four layers."""
    config = dict(config, **TINY)
    config["published"] = dict(config["published"],
                               moe_num_primary_experts=TINY_ROUTED)
    return config, dict(traffic, batch_per_chip=2, seq=64)


def steady(plan, config, traffic, seeds, seconds):
    """``run.py``'s window without the check, for each seed: samples/s, the
    rows held at the first and the last step, the load's max/mean."""
    rows = []
    for seed in seeds:
        t = time.time()
        cell = plan.family.build(config, traffic, jax.devices()[:1], seed)
        cell.pipe.warmup(cell.state, cell.window)
        cell.first_dispatch()
        fetched, done_at, _, _ = run._measure(cell, seconds, None)
        load = smallthinker_flops.load_counts(
            {"moe_load": np.concatenate([np.ravel(f["moe_load"])
                                         for f in fetched])}, config)
        held = smallthinker_flops.held(config, load).astype(float)
        per_step = held.sum((-1, -2))
        ratio = (held.max(-1) / held.mean(-1)).max(-1)
        rate = cell.k * cell.samples_per_step / statistics.median(
            np.diff([0.0] + done_at))
        row = {"seed": seed, "samples_per_s": rate, "steps": len(per_step),
               "rows_held_first": int(per_step[0]),
               "rows_held_last": int(per_step[-1]),
               "load_max_over_mean": float(ratio.mean()),
               "seconds": time.time() - t}
        print("STEADY " + json.dumps(row), flush=True)
        rows.append(row)
        jax.tree_util.tree_map(lambda x: x.delete(), cell.state)
        del cell
    rates = [r["samples_per_s"] for r in rows]
    if len(rates) >= 2:
        q = statistics.quantiles(rates, n=4)
        print(f"STEADY spread {(q[2] - q[0]) / statistics.median(rates):.5f} "
              f"median {statistics.median(rates):.1f}", flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=2147484001)
    p.add_argument("--variants", default="base,bf16_parts,bf16_router")
    p.add_argument("--tiny", type=int, default=0)
    p.add_argument("--steady", type=int, default=0)
    p.add_argument("--seeds", default="2147484001")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--weights-seed", type=int, default=-1,
                   help="the weights' key (-1: the recipe's)")
    p.add_argument("--lr", type=float, default=None)
    a = p.parse_args()
    from apex_tpu import cache

    print(f"compile cache: {cache.enable()}", flush=True)
    plan = run.resolve(CELL)
    config, traffic = plan.config, plan.traffic
    if a.tiny:
        config, traffic = tiny(config, traffic)
    if a.lr is not None:
        config = dict(config, recipe=dict(config["recipe"], lr=a.lr))
    if a.weights_seed >= 0:
        config = dict(config, recipe=dict(config["recipe"],
                                          weights_key=a.weights_seed))
    if a.steady:
        steady(plan, config, traffic,
               [int(s) for s in a.seeds.split(",")], a.seconds)
        return

    reference_mean = plan.family.reference_mean

    def timed(p0, x, y, rows, cfg, forced=None):
        # of the configuration as it is, under the variant's selection
        t = time.time()
        mean = reference_mean(p0, x, y, rows, config, forced)
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
            if variant == "half_sequence":
                cell.pipe = first_half_twice(cell.pipe)
            print(f"VERDICT {variant} seed {a.seed}: "
                  + json.dumps(cell.check()) + f" ({time.time() - t:.1f} s)",
                  flush=True)
            jax.tree_util.tree_map(lambda x: x.delete(), cell.state.params)
            del cell
        except Exception:
            print(f"VERDICT {variant} seed {a.seed}: raised", flush=True)
            traceback.print_exc()
    degrade("base", config)


if __name__ == "__main__":
    main()
