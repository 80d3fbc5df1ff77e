"""The upper readings of ``granite4_h_micro_o2``'s tolerance: the cell's own
comparison run on systems that have to come out as not correct.  For the
chip, at the cell's size (not a pytest file)::

    python tests/benchmark/granite_hybrid_controls.py --seed 2147484001 \
        --variants base,bf16_scan,int8_proj,no_skip,no_logits_scaling,half_batch

Every variant goes through the family's ``build()``, ``first_dispatch()`` and
``check()`` as ``run.py`` drives them, so what is read is the timed
executable's step; the reference's gradient is computed once and kept on the
host.  One ``VERDICT`` line per variant, then every kind of
leaf's worst error against its own norm.

``bf16_scan``: the scan's float32 parts (dt, A, running sums, decays,
accumulators) in bf16, the nearest precision below the configuration's.
``int8_proj``: every projection's operands rounded to int8, one scale a
tensor.  ``no_skip``: the term ``D x`` left out of every mixer.
``no_logits_scaling``: the logits not divided by 8.  ``half_batch``: the
step trains on the first sequence twice.  ``--tiny 1`` cuts the widths for a
CPU rehearsal.
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

from apex_tpu.models import granite_hybrid  # noqa: E402
from apex_tpu.ops import ssd  # noqa: E402
from benchmark import compare, run  # noqa: E402

CELL = "granite4_h_micro_o2.b2_seq4096"
TINY = dict(vocab_size=512, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, shared_intermediate_size=128,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=16, num_hidden_layers=3,
            layer_types=["mamba", "attention", "mamba"])


def _bf16_scan():
    source = open(ssd.__file__, encoding="utf-8").read()
    marked = "f32, cdt = jnp.float32, x.dtype"
    assert marked in source
    scope = {"__name__": "ssd_bf16"}
    exec(compile(source.replace(marked, "f32, cdt = jnp.bfloat16, x.dtype"),
                 "ssd_bf16", "exec"), scope)
    return scope["ssd_scan"]


def _fake_int8(x):
    scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127
    q = (jnp.round(x.astype(jnp.float32) / scale) * scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def _int8_dense(*a, **kw):
    return REAL["dense"](*a, dot_general=lambda lhs, rhs, *b, **k: (
        jax.lax.dot_general(_fake_int8(lhs), _fake_int8(rhs), *b, **k)), **kw)


REAL = {"dense": nn.DenseGeneral, "scan": granite_hybrid.ssd_scan,
        "verdict": compare.verdict}


def degrade(variant, config):
    """Plants the variant in the program and returns the configuration the
    family builds from."""
    granite_hybrid.ssd_scan, nn.DenseGeneral = REAL["scan"], REAL["dense"]
    if variant == "bf16_scan":
        granite_hybrid.ssd_scan = _bf16_scan()
    elif variant == "no_skip":
        granite_hybrid.ssd_scan = lambda x, dt, A, B, C, D, **kw: REAL["scan"](
            x, dt, A, B, C, None, **kw)
    elif variant == "int8_proj":
        nn.DenseGeneral = _int8_dense
    elif variant == "no_logits_scaling":
        return dict(config, logits_scaling=1)
    elif variant not in ("base", "half_batch"):
        raise SystemExit(f"unknown variant {variant!r}")
    return config


def first_sequence_twice(pipe):
    def step_window(state, window, k):
        return pipe.step_window(state, jax.tree_util.tree_map(
            lambda a: jnp.concatenate([a[:, :1]] * a.shape[1], axis=1),
            window), k)
    return types.SimpleNamespace(step_window=step_window)


def verdict_with_leaves(sys_loss, ref_loss, sys_grads, ref_grads, tol,
                        noise=None):
    """``compare.verdict`` and, printed, every kind of leaf's worst error
    against its own norm with its share of the whole gradient's norm."""
    def leaves(tree):                   # one float64 leaf at a time
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            yield jax.tree_util.keystr(path), np.asarray(leaf, np.float64).ravel()

    def by_kind():
        whole = np.sqrt(sum(float(r @ r) for _, r in leaves(ref_grads)))
        kinds = collections.defaultdict(lambda: (0.0, None, 0.0))
        for (name, s), (_, r) in zip(leaves(sys_grads), leaves(ref_grads)):
            own = np.sqrt(float(r @ r))
            err = np.sqrt(float((s - r) @ (s - r))) / max(own, 1e-300)
            kind = re.sub(r"\['layer_\d+'\]", "", name)
            if err >= kinds[kind][0]:
                kinds[kind] = (err, name, own / whole)
        return kinds

    kinds = by_kind()
    print("  by kind (worst leaf's error over its own norm, its share of the "
          "whole norm): " + "; ".join(
              f"{kind} {err:.4f} {share:.2e}"
              for kind, (err, _, share) in sorted(kinds.items())), flush=True)
    return REAL["verdict"](sys_loss, ref_loss, sys_grads, ref_grads, tol, noise)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=2147484001)
    p.add_argument("--variants", default="base,bf16_scan,int8_proj,no_skip,"
                                         "no_logits_scaling,half_batch")
    p.add_argument("--tiny", type=int, default=0)
    a = p.parse_args()
    from apex_tpu import cache

    print(f"compile cache: {cache.enable()}", flush=True)
    plan = run.resolve(CELL)
    config, traffic = plan.config, plan.traffic
    if a.tiny:
        config, traffic = dict(config, **TINY), dict(traffic, seq=64)

    kept, reference_mean = {}, plan.family.reference_mean

    def once(p0, x, y, rows, cfg):      # of the configuration as it is
        if "mean" not in kept:
            t = time.time()
            kept["mean"] = reference_mean(p0, x, y, rows, config)
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
                cell.pipe = first_sequence_twice(cell.pipe)
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
