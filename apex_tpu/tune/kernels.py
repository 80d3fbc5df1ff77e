"""Builtin kernel registrations — every Pallas kernel family in the
repo declares its config space here (ISSUE 14).

Imported lazily by :func:`apex_tpu.tune.registry.load_builtin` (the
tuner/CLI side); the kernel modules themselves only import the light
``tune.space``/``tune.dispatch`` halves, so there is no import cycle.

Per-spec notes:

* **flash_attention** (fwd+bwd) — ``block_q``/``block_k`` over the
  MXU-friendly multiples of 128 that tile the sequence; the tune case
  runs ``value_and_grad`` through the custom VJP so the fused backward
  kernel is most of the measured clock, exactly as in training.  The
  online-softmax recurrence reorders with the KV block, so the oracle
  checks to tolerance, not bitwise.
* **fused_layer_norm / xentropy** — ``row_block``
  sweeps; row partitioning never changes per-row math, so candidates
  must match the default config BITWISE.
* **quantized_matmul** — ``block_m``/``block_n`` tiles; each output
  element is an int32 dot over the full K regardless of tile, so the
  oracle is bitwise too.

Candidate priority (the ledger hook): memory-bound verdicts visit
smaller blocks first (layout/pipelining candidates — more grid steps,
less VMEM residency per byte), compute-bound verdicts visit bigger
tiles first (amortize the per-block epilogue over more MXU work —
the r4 flash sweep's measured gradient).
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp

from .registry import KernelSpec, TuneCase, register
from . import space as _space

# The kernel packages re-export their public functions from __init__
# (``apex_tpu.ops.flash_attention`` the ATTRIBUTE is the function), so
# module access goes through importlib.
import importlib


def _mod(name):
    return importlib.import_module("apex_tpu." + name)

__all__ = ["FLASH_ATTENTION", "FUSED_LAYER_NORM", "XENTROPY",
           "QUANTIZED_MATMUL"]

#: generous flash-kernel VMEM estimate budget (operand + score blocks +
#: scratch; the proven-on-chip 1024x1024 default must pass)
_FLASH_VMEM_BUDGET = int(14e6)


def _area_priority(area: float, bound: Optional[str]) -> float:
    # ascending visit order: memory-bound -> small blocks first,
    # compute-bound (and None) -> big tiles first
    return area if bound == "memory" else -area


# -- flash attention (fwd + bwd) ----------------------------------------------

def _flash_dims(shape: Mapping):
    return (int(shape.get("batch", 1)), int(shape.get("heads", 2)),
            int(shape.get("q_len", 1024)), int(shape.get("kv_len", 1024)),
            int(shape.get("head_dim", 64)),
            bool(shape.get("causal", True)),
            jnp.dtype(shape.get("dtype", "float32")))


def _flash_block_legal(t: int, blk: int) -> bool:
    fa = _mod("ops.flash_attention")
    return fa._pick_block(t, blk) == (blk if t > blk else t)


def _flash_fits(shape: Mapping, cfg: Dict[str, int]) -> bool:
    _, _, tq, tk, d, _, dtype = _flash_dims(shape)
    bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
    if not (_flash_block_legal(tq, bq) and _flash_block_legal(tk, bk)):
        return False
    isz = dtype.itemsize
    # two live fp32 [bq, bk] score/prob blocks + fp32 acc + operand
    # blocks + the [bq, 1] row stats
    est = (8 * bq * bk + 4 * bq * d + isz * (bq + 2 * bk) * d + 8 * bq)
    return est <= _FLASH_VMEM_BUDGET


def _flash_defaults(shape: Mapping) -> Dict[str, int]:
    fa = _mod("ops.flash_attention")
    _, _, tq, tk, _, _, _ = _flash_dims(shape)
    bq = fa._pick_block(tq, fa._DEFAULT_BLOCK_Q)
    bk = fa._pick_block(tk, fa._DEFAULT_BLOCK_K)
    return {"block_q": int(bq or min(tq, fa._DEFAULT_BLOCK_Q)),
            "block_k": int(bk or min(tk, fa._DEFAULT_BLOCK_K))}


def _flash_candidates(shape: Mapping, bound: Optional[str]
                      ) -> List[Dict[str, int]]:
    _, _, tq, tk, _, _, _ = _flash_dims(shape)
    sizes = (128, 256, 512, 1024, 2048)
    out = []
    for bq in sizes:
        if bq > tq:
            continue
        for bk in sizes:
            if bk > tk:
                continue
            cfg = {"block_q": bq, "block_k": bk}
            if _flash_fits(shape, cfg):
                out.append(cfg)
    return out


def _flash_case(shape: Mapping, interpret: bool) -> TuneCase:
    import jax.random as jrandom
    flash_attention = _mod("ops.flash_attention").flash_attention
    b, h, tq, tk, d, causal, dtype = _flash_dims(shape)
    kq, kk, kv = jrandom.split(jrandom.PRNGKey(0), 3)
    q = (jrandom.normal(kq, (b, tq, h, d), jnp.float32) * 0.3).astype(dtype)
    k = (jrandom.normal(kk, (b, tk, h, d), jnp.float32) * 0.3).astype(dtype)
    v = (jrandom.normal(kv, (b, tk, h, d), jnp.float32) * 0.3).astype(dtype)
    fns: Dict[tuple, object] = {}

    def run(cfg):
        key = (int(cfg["block_q"]), int(cfg["block_k"]))
        f = fns.get(key)
        if f is None:
            bq, bk = key

            def loss(q, k, v):
                o = flash_attention(q, k, v, causal=causal, block_q=bq,
                                    block_k=bk, interpret=interpret)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            f = fns[key] = jax.jit(
                jax.value_and_grad(loss, argnums=(0, 1, 2)))
        return f(q, k, v)

    def ref():
        blockwise_attention = _mod("ops.attention").blockwise_attention

        def loss(q, k, v):
            o = blockwise_attention(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    return TuneCase(run=run, ref=ref, tol=(2e-2, 2e-3))


def _flash_bucket(shape: Mapping) -> str:
    fa = _mod("ops.flash_attention")
    _, _, tq, tk, d, causal, _ = _flash_dims(shape)
    return fa.tune_bucket(tq, tk, d, causal, False, False)


def _flash_version() -> int:
    fa = _mod("ops.flash_attention")
    return fa.TUNE_VERSION


def _flash_effective(shape: Mapping, cfg: Dict[str, int]):
    fa = _mod("ops.flash_attention")
    _, _, tq, tk, _, _, _ = _flash_dims(shape)
    return (fa._pick_block(tq, int(cfg["block_q"])),
            fa._pick_block(tk, int(cfg["block_k"])))


FLASH_ATTENTION = register(KernelSpec(
    name="flash_attention", version=_flash_version(),
    params=("block_q", "block_k"), kind="compute", exact=False,
    defaults=_flash_defaults, candidates=_flash_candidates,
    constraint=_flash_fits, build=_flash_case, bucket=_flash_bucket,
    priority=lambda shape, cfg, bound: _area_priority(
        cfg["block_q"] * cfg["block_k"], bound),
    effective=_flash_effective,
    example_shape={"batch": 1, "heads": 8, "q_len": 4096, "kv_len": 4096,
                   "head_dim": 64, "causal": True, "dtype": "bfloat16"},
    small_shape={"batch": 1, "heads": 2, "q_len": 256, "kv_len": 256,
                 "head_dim": 64, "causal": True, "dtype": "float32"},
    regions=("attention", "flash", "attn")))


# -- row-blocked elementwise kernels ------------------------------------------

def _rows_priority(cfg, bound):
    return _area_priority(cfg["row_block"], bound)


def _ln_dims(shape: Mapping):
    return (int(shape.get("n1", 8192)), int(shape.get("n2", 1024)),
            jnp.dtype(shape.get("dtype", "float32")))


def _ln_candidates(shape: Mapping, bound: Optional[str]):
    n1, n2, dtype = _ln_dims(shape)
    # the backward block is the worst case (g, x, dx + 4 fp32 temps)
    blocks = _space.row_block_candidates(n1, n2, 3 * dtype.itemsize + 16)
    return [{"row_block": b} for b in blocks]


def _ln_constraint(shape: Mapping, cfg: Dict[str, int]) -> bool:
    _, n2, dtype = _ln_dims(shape)
    return cfg["row_block"] % _space.SUBLANE_ROWS == 0 \
        and _space.floor_block_fits(n2, 3 * dtype.itemsize + 16)


def _ln_case(shape: Mapping, interpret: bool) -> TuneCase:
    import jax.random as jrandom
    fused_layer_norm = _mod("normalization.fused_layer_norm").fused_layer_norm
    n1, n2, dtype = _ln_dims(shape)
    x = (jrandom.normal(jrandom.PRNGKey(0), (n1, n2), jnp.float32)
         ).astype(dtype)
    w = jnp.linspace(0.5, 1.5, n2, dtype=jnp.float32)
    b = jnp.linspace(-0.1, 0.1, n2, dtype=jnp.float32)
    fns: Dict[int, object] = {}

    def grad_fn(**kw):
        def loss(x, w, b):
            o = fused_layer_norm(x, (n2,), w, b, **kw)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    def run(cfg):
        rb = int(cfg["row_block"])
        f = fns.get(rb)
        if f is None:
            f = fns[rb] = grad_fn(impl="pallas", row_block=rb,
                                  interpret=interpret)
        return f(x, w, b)

    return TuneCase(run=run, ref=lambda: grad_fn(impl="jnp")(x, w, b))


def _ln_bucket(shape: Mapping) -> str:
    fln = _mod("normalization.fused_layer_norm")
    n1, n2, dtype = _ln_dims(shape)
    return fln.tune_bucket(n1, n2, dtype.itemsize)


def _ln_version() -> int:
    fln = _mod("normalization.fused_layer_norm")
    return fln.TUNE_VERSION


def _ln_effective(shape: Mapping, cfg: Dict[str, int]):
    n1, n2, dtype = _ln_dims(shape)
    isz = dtype.itemsize
    # (fwd, bwd) effective blocks — both clamps must agree for two
    # configs to be the same program
    return (_space.pick_rows(n1, n2, 2 * isz + 12,
                             row_block=cfg["row_block"]),
            _space.pick_rows(n1, n2, 3 * isz + 16,
                             row_block=cfg["row_block"]))


FUSED_LAYER_NORM = register(KernelSpec(
    name="fused_layer_norm", version=_ln_version(),
    params=("row_block",), kind="memory", exact=True,
    defaults=lambda shape: {"row_block": 256},
    candidates=_ln_candidates, constraint=_ln_constraint,
    build=_ln_case, bucket=_ln_bucket,
    priority=lambda shape, cfg, bound: _rows_priority(cfg, bound),
    effective=_ln_effective,
    example_shape={"n1": 8192, "n2": 1024, "dtype": "bfloat16"},
    small_shape={"n1": 64, "n2": 128, "dtype": "float32"},
    regions=("layer_norm", "layernorm", "ln")))


def _xe_dims(shape: Mapping):
    return (int(shape.get("rows", 4096)), int(shape.get("vocab", 8192)))


def _xe_candidates(shape: Mapping, bound: Optional[str]):
    xe = _mod("contrib.xentropy")
    n, h = _xe_dims(shape)
    out, seen = [], set()
    for blk in (8, 16, 32, 64, 128, 256, 512):
        eff = xe._row_block(n, h, blk)
        if eff in seen:
            continue
        seen.add(eff)
        out.append({"row_block": blk})
    return out


def _xe_constraint(shape: Mapping, cfg: Dict[str, int]) -> bool:
    xe = _mod("contrib.xentropy")
    _, h = _xe_dims(shape)
    return cfg["row_block"] % _space.SUBLANE_ROWS == 0 \
        and xe._pallas_fits(h)


def _xe_case(shape: Mapping, interpret: bool) -> TuneCase:
    import jax.random as jrandom
    xe = _mod("contrib.xentropy")
    n, h = _xe_dims(shape)
    logits = jrandom.normal(jrandom.PRNGKey(0), (n, h), jnp.float32)
    labels = jrandom.randint(jrandom.PRNGKey(1), (n,), 1, h, jnp.int32)
    g = jnp.linspace(0.5, 1.5, n, dtype=jnp.float32)
    fns: Dict[int, object] = {}

    def run(cfg):
        rb = int(cfg["row_block"])
        f = fns.get(rb)
        if f is None:
            def both(logits, g):
                # the loss as it runs under differentiation: one kernel
                # that leaves ``r`` over its logits (here a copy of them:
                # the case keeps its input), and the VJP's row scaling
                losses, r = xe._fwd_grad_pallas(logits, labels, 0.1,
                                                interpret, rb)
                return losses, g[:, None] * r

            f = fns[rb] = jax.jit(both)
        return f(logits, g)

    def ref():
        def both(logits, g):
            losses, mlse = xe._fwd_ref(logits, labels, 0.1)
            return losses, xe._bwd_ref(g, logits, mlse, labels, 0.1)

        return jax.jit(both)(logits, g)

    return TuneCase(run=run, ref=ref)


def _xe_bucket(shape: Mapping) -> str:
    xe = _mod("contrib.xentropy")
    n, h = _xe_dims(shape)
    return xe.tune_bucket(n, h)


def _xe_version() -> int:
    xe = _mod("contrib.xentropy")
    return xe.TUNE_VERSION


def _xe_effective(shape: Mapping, cfg: Dict[str, int]):
    xe = _mod("contrib.xentropy")
    n, h = _xe_dims(shape)
    return xe._row_block(n, h, cfg["row_block"])


XENTROPY = register(KernelSpec(
    name="xentropy", version=_xe_version(),
    params=("row_block",), kind="memory", exact=True,
    defaults=lambda shape: {"row_block": 128},
    candidates=_xe_candidates, constraint=_xe_constraint,
    build=_xe_case, bucket=_xe_bucket,
    priority=lambda shape, cfg, bound: _rows_priority(cfg, bound),
    effective=_xe_effective,
    example_shape={"rows": 4096, "vocab": 8192},
    small_shape={"rows": 32, "vocab": 128},
    regions=("xent", "loss", "softmax", "cross_entropy")))


# -- quantized matmul ---------------------------------------------------------

def _qmm_dims(shape: Mapping):
    return (int(shape.get("m", 8192)), int(shape.get("k", 4096)),
            int(shape.get("n", 4096)),
            jnp.dtype(shape.get("dtype", "bfloat16")))


def _qmm_candidates(shape: Mapping, bound: Optional[str]):
    m, k, n, dtype = _qmm_dims(shape)
    out = []
    for bm in (64, 128, 256, 512):
        for bn in (128, 256, 512):
            cfg = {"block_m": bm, "block_n": bn}
            if _qmm_constraint(shape, cfg):
                out.append(cfg)
    return out


def _qmm_constraint(shape: Mapping, cfg: Dict[str, int]) -> bool:
    qk = _mod("quant.kernels")
    m, k, n, dtype = _qmm_dims(shape)
    bm = qk._pick_block(m, int(cfg["block_m"]), 8)
    bn = qk._pick_block(n, int(cfg["block_n"]), 128)
    return qk._kernel_fits(bm, bn, k, dtype.itemsize)


def _qmm_case(shape: Mapping, interpret: bool) -> TuneCase:
    import jax.random as jrandom
    quantized_matmul = _mod("quant.kernels").quantized_matmul
    m, k, n, dtype = _qmm_dims(shape)
    x = (jrandom.normal(jrandom.PRNGKey(0), (m, k), jnp.float32) * 0.05
         ).astype(dtype)
    w = (jrandom.normal(jrandom.PRNGKey(1), (k, n), jnp.float32) * 0.05
         ).astype(dtype)
    # frozen calibration constant for the synthetic normal(0, 0.05)
    # activations (amax ~5 sigma); NOT a per-call absmax — J014's rule
    x_scale = 0.25 / 127.0
    fns: Dict[tuple, object] = {}

    def grad_fn(**kw):
        def loss(x, w):
            o = quantized_matmul(x, w, x_scale=x_scale, **kw)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    def run(cfg):
        key = (int(cfg["block_m"]), int(cfg["block_n"]))
        f = fns.get(key)
        if f is None:
            f = fns[key] = grad_fn(impl="pallas", interpret=interpret,
                                   block_m=key[0], block_n=key[1])
        return f(x, w)

    return TuneCase(run=run, ref=lambda: grad_fn(impl="jnp")(x, w))


def _qmm_bucket(shape: Mapping) -> str:
    qk = _mod("quant.kernels")
    m, k, n, dtype = _qmm_dims(shape)
    return qk.tune_bucket(m, k, n, dtype.itemsize)


def _qmm_version() -> int:
    qk = _mod("quant.kernels")
    return qk.TUNE_VERSION


def _qmm_effective(shape: Mapping, cfg: Dict[str, int]):
    qk = _mod("quant.kernels")
    m, k, n, _ = _qmm_dims(shape)
    return (qk._pick_block(m, int(cfg["block_m"]), 8),
            qk._pick_block(n, int(cfg["block_n"]), 128))


QUANTIZED_MATMUL = register(KernelSpec(
    name="quantized_matmul", version=_qmm_version(),
    params=("block_m", "block_n"), kind="compute", exact=True,
    defaults=lambda shape: {"block_m": 256, "block_n": 256},
    candidates=_qmm_candidates, constraint=_qmm_constraint,
    build=_qmm_case, bucket=_qmm_bucket,
    priority=lambda shape, cfg, bound: _area_priority(
        cfg["block_m"] * cfg["block_n"], bound),
    effective=_qmm_effective,
    example_shape={"m": 8192, "k": 4096, "n": 4096, "dtype": "bfloat16"},
    small_shape={"m": 64, "k": 128, "n": 128, "dtype": "float32"},
    regions=("quant", "qmm", "dense", "proj", "mlp")))
