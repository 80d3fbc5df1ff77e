"""Flash-vs-jnp attention crossover sweep (VERDICT r4 next #2).

The Pallas flash kernel wins at long context (~3x at seq 8k) and LOSES at
short context: at BERT's seq 128 the per-kernel-launch overhead and the
1024^2-tuned block machinery cannot beat one fused XLA softmax over a
[B,H,128,128] score tensor that fits VMEM outright.  This sweep measures
fwd+bwd wall time of the three implementations over (seq, heads*batch,
head_dim, causal) on the real chip and prints a JSON table; the measured
crossover is baked into ``apex_tpu.ops.flash_attention`` as the default
dispatch rule (and documented in ``docs/attention.md``).

Run on the TPU host (from the sandbox: through ``chiprun``)::

    python tools/attention_sweep.py --out chiprun_out/attention_sweep.json

Timing policy: min-of-3 passes of ``iters`` fwd+bwd calls, execution
forced by a scalar fetch of the last output (dispatch is asynchronous).
No sweep of the current installation is on file: the rows the dispatch
threshold rests on are quoted in ``docs/attention.md``.
"""

from __future__ import annotations

import argparse
import json
import os as _os
import sys as _sys
import time

import numpy as np

try:
    import apex_tpu  # noqa: F401
except ModuleNotFoundError:
    _sys.path.insert(0, _os.path.abspath(_os.path.join(
        _os.path.dirname(__file__), _os.pardir)))

import jax
import jax.numpy as jnp


def _force(x):
    """Force execution via one scalar device->host fetch: the device runs
    enqueued programs in order, so fetching one element of the LAST
    output drains everything before it."""
    return float(jnp.ravel(x)[0].astype(jnp.float32))


def time_grad(fn, q, k, v, iters=10, reps=3):
    """Min-of-reps seconds per fwd+bwd call of a 3-arg grad."""
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    out = g(q, k, v)
    _force(out[0])
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(q, k, v)
        _force(out[0])
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def sweep(configs, iters=10):
    from apex_tpu.ops.attention import blockwise_attention
    from apex_tpu.ops.attention import dot_product_attention
    from apex_tpu.ops.flash_attention import flash_attention

    rows = []
    rng = np.random.RandomState(0)
    for cfg in configs:
        b, s, h, d, causal = (cfg["batch"], cfg["seq"], cfg["heads"],
                              cfg["head_dim"], cfg["causal"])
        q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
                   for _ in range(3))
        row = dict(cfg)
        # full (materialized scores) — skip where the [B,H,T,S] tensor
        # would blow HBM (fp32 scores + softmax residents, fwd AND bwd)
        score_gb = 4 * b * h * s * s / 1e9
        if score_gb < 4.0:
            row["full_ms"] = round(time_grad(
                lambda q, k, v: dot_product_attention(q, k, v,
                                                      causal=causal),
                q, k, v, iters) * 1e3, 3)
        row["blockwise_ms"] = round(time_grad(
            lambda q, k, v: blockwise_attention(q, k, v, causal=causal),
            q, k, v, iters) * 1e3, 3)
        # flash kernel at candidate block sizes (block <= seq only)
        best_flash, best_blk = None, None
        for blk in cfg.get("blocks", [128, 256, 512, 1024]):
            if blk > s:
                continue
            t = time_grad(
                lambda q, k, v, blk=blk: flash_attention(
                    q, k, v, causal=causal, block_q=blk, block_k=blk),
                q, k, v, iters) * 1e3
            row[f"flash_{blk}_ms"] = round(t, 3)
            if best_flash is None or t < best_flash:
                best_flash, best_blk = t, blk
        if best_flash is None:         # no candidate block tiles this seq
            row["flash_best_ms"] = None
            row["kernel_wins"] = False
        else:
            row["flash_best_ms"] = round(best_flash, 3)
            row["flash_best_block"] = best_blk
            # jaxlint: disable=J001 -- best_flash is time_grad's host float (min-of-reps seconds), not a device value
            row["kernel_wins"] = bool(
                best_flash < min(row.get("full_ms", float("inf")),
                                 row["blockwise_ms"]))
        row["jnp_best_ms"] = round(min(row.get("full_ms", float("inf")),
                                       row["blockwise_ms"]), 3)
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--quick", action="store_true",
                    help="CPU smoke: tiny shapes, interpret-free jnp only")
    args = ap.parse_args()

    if args.quick:
        configs = [dict(batch=2, seq=128, heads=2, head_dim=64,
                        causal=False, blocks=[128])]
    else:
        configs = []
        # BERT-shaped batch (b16 h12 d64) at fine-grained short seqs —
        # where the crossover lives; non-causal (encoder) AND causal.
        for causal in (False, True):
            for s in (128, 256, 512, 1024, 2048):
                configs.append(dict(batch=16, seq=s, heads=12, head_dim=64,
                                    causal=causal))
        # long-context single-batch (the flash headline shape), causal.
        for s in (4096, 8192):
            configs.append(dict(batch=1, seq=s, heads=12, head_dim=64,
                                causal=True))
        # head_dim=128 spot checks (GPT-ish) at the crossover region.
        for s in (256, 512, 1024):
            configs.append(dict(batch=8, seq=s, heads=16, head_dim=128,
                                causal=True))

    rows = sweep(configs, iters=args.iters)
    out = {"device_kind": jax.devices()[0].device_kind,
           "backend": jax.default_backend(),
           "timing_policy": "min_of_3_passes",
           "iters": args.iters,
           "rows": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"n_rows": len(rows),
                      "kernel_wins_from_seq": min(
                          [r["seq"] for r in rows if r["kernel_wins"]],
                          default=None)}))


if __name__ == "__main__":
    main()
