"""Flash attention — Pallas TPU kernels with custom VJP.

Beyond-parity component (the reference has no attention code at all,
SURVEY.md §5 "Long-context"): the hot op of every transformer, built the
TPU way.  The jnp blockwise path (``apex_tpu/ops/attention.py``) is the
numerics oracle and the off-TPU fallback; the kernels here keep the whole
online-softmax recurrence in VMEM so the [T, S] score matrix never touches
HBM in either direction.

Design:

* **forward** — grid ``(batch, heads, q_blocks, kv_blocks)`` with the KV
  block innermost; VMEM scratch carries the running (row-max ``m``,
  denominator ``l``, unnormalized accumulator ``acc``) across KV steps and
  the output + logsumexp are written on the last step.  Saving only
  ``lse = m + log l`` (one fp32 per row) is what makes the backward
  recompute exact — the same memory trick as the reference's fused
  xentropy kernel (``csrc/xentropy_kernel.cu`` saves max_log_sum_exp).
* **backward** — two kernels, both recomputing ``p = exp(s - lse)``:
  ``dq`` iterates KV blocks innermost (accumulating ``ds @ k``), ``dk/dv``
  iterates Q blocks innermost.  Every matmul is expressed in the natural
  ``[bq, bk]`` orientation with leading-dim contractions where the output
  is K-major, so no operand ever needs a VMEM relayout/transpose.
  ``delta = rowsum(do * o)`` is a cheap jnp reduction fused by XLA.
* causal masking skips fully-masked KV blocks via ``pl.when`` predication,
  and sliding-window local attention goes further with a BOUNDED grid:
  only ``ceil(window/bk)+1`` KV blocks per Q block are even visited
  (virtual-negative block ids clamp in the index maps and predicate off),
  so local attention is O(T * window) in both compute and fetches;
  a key-side additive bias ``[batch, kv_len]`` covers padding masks and a
  head-broadcast ``[batch, q_len, kv_len]`` bias covers segment/2-D masks
  and relative-position biases, with its head-summed gradient produced by
  a dedicated third backward kernel (grid head-innermost so the output
  block accumulates residently).  A per-head ``[B,H,T,S]`` bias falls
  back to the jnp path.
* per-row stats (``lse``, ``delta``) travel as ``[B, H, T, 1]`` so kernel
  blocks are ``(bq, 1)`` column vectors — the layout the FusedLayerNorm
  kernel already uses for mean/invvar — avoiding lane-replication waste.

All matmuls run on the MXU with fp32 accumulation
(``preferred_element_type``); ``p`` is cast back to the value dtype before
the PV matmul so bf16 inputs stay on the fast path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:  # TPU-only import; absent on CPU-only installs.
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from ..normalization.fused_layer_norm import _use_pallas
from ..pallas_compat import align_vma as _align_vma
from ..pallas_compat import mxu_dot as _mxu_dot
from ..pallas_compat import sds_with_vma as _sds
from ..tune.dispatch import kernel_config as _tuned_config
from ..tune.space import pow2_bucket as _pow2

NEG_INF = -1e30

#: config-cache version of this kernel family's blocking scheme
#: (ISSUE 14) — covers the forward AND both backward kernels (they
#: share block_q/block_k); bump when the grid/block semantics change.
TUNE_VERSION = 1
# r4 block-size sweep on the v5e (seq 8k causal fwd+bwd, min-of-3):
# 512x512 18.45 ms, 1024x512 17.50, 512x1024 16.44, 1024x1024 15.75,
# 2048x512 17.78, 256x256 27.99 — bigger blocks amortize the per-block
# mask/softmax epilogue over more MXU work; 1024^2 scores (4 MB fp32)
# still fit VMEM comfortably beside the operands.
_DEFAULT_BLOCK_Q = 1024
_DEFAULT_BLOCK_K = 1024

# Shape dispatch (r5, VERDICT r4 next #2): at short sequence the Pallas
# kernels LOSE to one fused XLA softmax over materialized scores — the
# per-launch overhead and block machinery cannot amortize (BERT seq 128:
# 27.7% of the device step was zero-attributed custom-calls).  Measured
# crossover on a v5e under an earlier installation (tools/
# attention_sweep.py, 15 configs over seq x head_dim x batch*heads x
# causal, rows in docs/attention.md; not re-measured on this one): below
# 1024 the jnp path wins or ties within noise (e.g. causal b16 s512: jnp
# 9.7 ms vs kernel-best 12.4); from 1024 the kernel wins decisively
# (causal b16 s1024: 12.4 vs 21.6; s2048: 18.8 vs 47.7; 1024^2 blocks
# best at every winning shape).  flash_attention with DEFAULT (None)
# block sizes routes sub-crossover shapes to the jnp path, which computes
# the same function; passing block_q/block_k explicitly always forces
# the kernel (the escape hatch, same contract as the bias cap).
_KERNEL_MIN_KV = 1024


def tune_bucket(tq: int, tk: int, d: int, causal: bool, has_bias: bool,
                windowed: bool) -> str:
    """Config-cache shape bucket: sequence lengths round up to powers of
    two (the block sweep's winners are stable within a pow2 band, r4);
    head_dim, causality, the [B,T,S]-bias flag (extra VMEM residents per
    block) and the sliding-window flag (bounded grid wants bq == bk) are
    exact."""
    return (f"q{_pow2(tq)}_k{_pow2(tk)}_d{d}_c{int(causal)}"
            f"_b{int(has_bias)}_w{int(windowed)}")


def _dispatch_to_jnp(tq, tk, defaults_used):
    """True when the defaults-only shape dispatch should take the jnp
    path: caller left both block sizes at their defaults AND the KV
    length is below the measured kernel-win crossover."""
    return defaults_used and tk < _KERNEL_MIN_KV and tq < _KERNEL_MIN_KV


def _pick_block(t: int, preferred: int) -> Optional[int]:
    """Largest block <= preferred that divides t and is a multiple of 128;
    or t itself when t <= preferred and sublane-aligned (t % 8 == 0 — a
    whole-array block equal to the array dim is legal in Mosaic).  None =
    no legal block, caller falls back to the jnp path."""
    if t <= preferred:
        return t if t % 8 == 0 else None
    preferred -= preferred % 128          # honor the multiple-of-128 claim
    for blk in range(preferred, 127, -128):
        if t % blk == 0:
            return blk
    return None


def _causal_block_mask(qi, ki, bq, bk, q_off=0, k_off=0, window=None):
    """Causal (optionally sliding-window) mask on GLOBAL positions:
    ``q_off``/``k_off`` are the global offsets of this call's first
    query/key row (dynamic scalars under ring attention, 0 for
    single-device use).  ``window``: each query sees only the last
    ``window`` keys (itself included) — mistral/longformer-style local
    attention."""
    q_pos = q_off + qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_off + ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _block_live(qi, ki, bq, bk, q_off, k_off, window):
    """Whether this (qi, ki) block intersects the causal/window band —
    the block-skip predicate shared by all four kernels.  Blocks past the
    diagonal AND blocks older than the window are skipped entirely, so
    sliding-window attention costs O(T * window), not O(T^2)."""
    run = q_off + qi * bq + bq - 1 >= k_off + ki * bk        # causal skip
    if window is not None:
        # newest key in block still inside the oldest query's window?
        run = jnp.logical_and(
            run, (q_off + qi * bq) - (k_off + ki * bk + bk - 1) < window)
    return run


def _window_span(window, bq, bk, q_offset, k_offset, nk):
    """Static KV-block count per Q block for the BOUNDED sliding-window
    grid, or None to keep the full masked grid.  Bounded requires equal
    block sizes and static zero offsets (the ring path's dynamic offsets
    shift the band per rank); a span covering the whole row buys nothing.
    The bounded grid is what makes `window` O(T * window): a masked-only
    implementation still FETCHES every skipped block."""
    if window is None or bq != bk:
        return None
    if not (isinstance(q_offset, int) and isinstance(k_offset, int)
            and q_offset == 0 and k_offset == 0):
        return None
    span = (window - 2) // bk + 2
    return span if span < nk else None


_mm = _mxu_dot          # fp32-accumulating MXU matmul, explicit precision


# -- forward kernel ------------------------------------------------------------

def _offsets_and_predicates(qi, ki, bq, bk, *, causal, dyn_off, qoff_ref,
                            koff_ref, q_off0, k_off0, window, window_span):
    """Shared causal-control logic: global offsets (SMEM scalars on the
    ring path, Python constants otherwise — r4, the constants let the
    plain path's comparisons fold) and the block-skip ``run`` predicate.
    ``run is True`` statically for non-causal kernels."""
    if not causal:
        return 0, 0, True
    if dyn_off:
        q_off, k_off = qoff_ref[0, 0], koff_ref[0, 0]
    else:
        q_off, k_off = q_off0, k_off0
    run = _block_live(qi, ki, bq, bk, q_off, k_off, window)
    if window_span is not None:
        run = jnp.logical_and(run, ki >= 0)
    return q_off, k_off, run


def _masked_split(run, body, mask_fn):
    """Run ``body(mask_fn())`` under the ``run`` block-skip predicate;
    ``run is True`` statically (non-causal) runs the unmasked body
    directly.

    r4 lesson (measured on chip, seq 8k causal): splitting into an
    unmasked interior branch + masked edge branch under complementary
    ``pl.when``s REGRESSED 17% (17.2 -> 20.1 ms fwd+bwd) — duplicating
    the matmul body across predicated regions defeats Mosaic's loop
    pipelining, which outweighs the saved per-element mask work.  One
    body, always masked on causal paths."""
    if run is True:
        body(None)
        return

    @pl.when(run)
    def _():
        body(mask_fn())


def _opt_refs(refs, has_bias, has_bias2, dyn_off):
    """Split a kernel's trailing refs into (kb, b2, qoff, koff, rest) per
    the operand-assembly flags — the single mirror of the conditional
    operand order both pallas callers build."""
    it = iter(refs)
    kb_ref = next(it) if has_bias else None
    b2_ref = next(it) if has_bias2 else None
    qoff_ref = next(it) if dyn_off else None
    koff_ref = next(it) if dyn_off else None
    return kb_ref, b2_ref, qoff_ref, koff_ref, list(it)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, sm_scale, causal, has_bias,
                has_bias2, dyn_off, q_off0, k_off0, window,
                window_span=None):
    kb_ref, b2_ref, qoff_ref, koff_ref, rest = _opt_refs(
        refs, has_bias, has_bias2, dyn_off)
    out_ref, lse_ref, m_scr, l_scr, acc_scr = rest

    j = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)
    # Bounded sliding-window grid (window_span set): only span KV blocks
    # per Q block are visited; j walks them ending at the diagonal (ki may
    # be a virtual negative for early rows -> dead step).
    ki = j if window_span is None else qi - (window_span - 1) + j
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: fully-masked KV blocks above the diagonal are skipped (on
    # global positions, so a ring shard entirely in the future runs no
    # block at all).
    q_off, k_off, run = _offsets_and_predicates(
        qi, ki, bq, bk, causal=causal, dyn_off=dyn_off, qoff_ref=qoff_ref,
        koff_ref=koff_ref, q_off0=q_off0, k_off0=k_off0, window=window,
        window_span=window_span)

    def body(mask):
        q = q_ref[0, 0]                                  # [bq, d]
        k = k_ref[0, 0]                                  # [bk, d]
        s = _mm(q, k, ((1,), (1,))) * sm_scale   # [bq, bk]
        if has_bias:
            s = s + kb_ref[0].astype(jnp.float32)
        if has_bias2:
            s = s + b2_ref[0].astype(jnp.float32)        # [bq, bk] block
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]                                # [bq, 1]
        l_prev = l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                  # [bq, 1]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = _mm(p.astype(v_ref.dtype), v_ref[0, 0],
                 ((1,), (0,)))                           # [bq, d]
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new
        l_scr[:] = l_new

    _masked_split(run, body,
                  lambda: _causal_block_mask(qi, ki, bq, bk, q_off, k_off,
                                             window))

    @pl.when(j == nk - 1)
    def _():
        l = l_scr[:]
        safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0, 0] = (acc_scr[:] / safe).astype(out_ref.dtype)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF,
                                  m_scr[:] + jnp.log(safe))


def _off_arg(offset):
    """Dynamic global-offset scalar as a (1, 1) SMEM operand."""
    return jnp.asarray(offset, jnp.int32).reshape(1, 1)


def _off_spec():
    # *_: the offset scalar is grid-invariant for every kernel regardless
    # of grid rank (the dkv grid is 5-D under GQA, 4-D otherwise).
    if pltpu is None:  # pragma: no cover
        return pl.BlockSpec((1, 1), lambda *_: (0, 0))
    return pl.BlockSpec((1, 1), lambda *_: (0, 0),
                        memory_space=pltpu.SMEM)


def _static_offsets(causal, q_offset, k_offset):
    """(dyn_off, q_off0, k_off0): offsets are baked as Python constants
    whenever they are static ints (the single-device path — r4, no SMEM
    operands / scalar reads in the kernels); traced scalars (the ring
    path) ride SMEM.  Non-causal kernels never read offsets at all."""
    if not causal:
        return False, 0, 0
    if isinstance(q_offset, int) and isinstance(k_offset, int):
        return False, int(q_offset), int(k_offset)
    return True, 0, 0


def _flash_fwd_pallas(q, k, v, kbias, *, sm_scale, causal, block_q, block_k,
                      q_offset=0, k_offset=0, qk_bias=None, window=None,
                      interpret=False):
    """q: [B, H, T, D]; k,v: [B, H_kv, S, D] (head-major) with
    ``H % H_kv == 0`` — grouped-query/multi-query attention shares each KV
    head across ``H / H_kv`` query heads purely through the k/v BlockSpec
    index maps (no repeat/materialization).  kbias: [B, S] or None.
    ``qk_bias``: [B, Tq, Tk] additive bias (broadcast over heads) or None.
    ``q_offset``/``k_offset``: global positions of the first query/key row
    (may be traced scalars — the ring-attention hook).
    Returns (out [B,H,T,D], lse [B,H,T,1] fp32).

    Operands are assembled per configuration (r4): the plain causal path
    carries NO bias dummies and NO offset scalars — what the r3 kernels
    paid for unconditionally (VERDICT r3 next #4)."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    grp = h // k.shape[1]                # query heads per KV head (GQA)
    nq, nk = tq // block_q, tk // block_k
    has_bias = kbias is not None
    has_bias2 = qk_bias is not None
    dyn_off, q_off0, k_off0 = _static_offsets(causal, q_offset, k_offset)

    span = _window_span(window, block_q, block_k, q_offset, k_offset, nk)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               has_bias=has_bias, has_bias2=has_bias2,
                               dyn_off=dyn_off, q_off0=q_off0, k_off0=k_off0,
                               window=window, window_span=span)
    if span is None:
        _kc = lambda qi, j: j
    else:          # clamped real block for a possibly-virtual ki
        _kc = lambda qi, j: jnp.maximum(qi - (span - 1) + j, 0)
    _hk = (lambda h: h) if grp == 1 else (lambda h: h // grp)

    ins = [q, k, v]
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, qi, j: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, qi, j: (b, _hk(h), _kc(qi, j), 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b, h, qi, j: (b, _hk(h), _kc(qi, j), 0)),
    ]
    if has_bias:
        ins.append(kbias[:, None, :])
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda b, h, qi, j: (b, 0, _kc(qi, j))))
    if has_bias2:
        ins.append(qk_bias)
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k), lambda b, h, qi, j: (b, qi, _kc(qi, j))))
    if dyn_off:
        ins += [_off_arg(q_offset), _off_arg(k_offset)]
        in_specs += [_off_spec(), _off_spec()]
    # Align varying-manual-axes across ALL operands (rank-varying ring
    # offsets vs replicated biases vs sharded activations) so the kernel
    # traces under shard_map's default vma tracking.  Rebind q/k/v to the
    # ALIGNED arrays: the out_shape vma below must carry the union vma
    # (e.g. a sharded bias over replicated activations).
    ins = list(_align_vma(*ins))
    q, k, v = ins[0], ins[1], ins[2]
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, span if span is not None else nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, qi, j: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, j: (b, h, qi, 0)),
        ],
        out_shape=[
            _sds((b, h, tq, d), q.dtype, q, k, v),
            _sds((b, h, tq, 1), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(*ins)
    return out, lse


# -- backward kernels ----------------------------------------------------------

def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kb_ref,
                    b2_ref, mask, *, sm_scale, has_bias, has_bias2):
    """Shared bwd recompute: returns (p, ds), both [bq, bk] fp32.
    ``mask`` is None on interior blocks (the r4 mask-free fast path)."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = _mm(q, k, ((1,), (1,))) * sm_scale       # [bq, bk]
    if has_bias:
        s = s + kb_ref[0].astype(jnp.float32)
    if has_bias2:
        s = s + b2_ref[0].astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0, 0])                           # lse: [bq, 1]
    if mask is not None:
        # A fully-masked row has lse == NEG_INF, making exp(NEG_INF -
        # NEG_INF) = 1 on masked entries; the forward kernel zeroes these,
        # so the recompute must too.
        p = jnp.where(mask, p, 0.0)
    dp = _mm(do_ref[0, 0], v_ref[0, 0], ((1,), (1,)))        # [bq, bk]
    ds = p * (dp - delta_ref[0, 0]) * sm_scale               # delta: [bq, 1]
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, sm_scale, causal, has_bias, has_bias2, dyn_off,
                   q_off0, k_off0, window, window_span=None):
    kb_ref, b2_ref, qoff_ref, koff_ref, rest = _opt_refs(
        refs, has_bias, has_bias2, dyn_off)
    dq_ref, dq_scr = rest
    j = pl.program_id(3)
    nk = pl.num_programs(3)
    qi = pl.program_id(2)
    ki = j if window_span is None else qi - (window_span - 1) + j
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_off, k_off, run = _offsets_and_predicates(
        qi, ki, bq, bk, causal=causal, dyn_off=dyn_off, qoff_ref=qoff_ref,
        koff_ref=koff_ref, q_off0=q_off0, k_off0=k_off0, window=window,
        window_span=window_span)

    def body(mask):
        _, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, kb_ref, b2_ref, mask,
                                sm_scale=sm_scale, has_bias=has_bias,
                                has_bias2=has_bias2)
        dq_scr[:] = dq_scr[:] + _mm(ds.astype(k_ref.dtype), k_ref[0, 0],
                                    ((1,), (0,)))

    _masked_split(run, body,
                  lambda: _causal_block_mask(qi, ki, bq, bk, q_off, k_off,
                                             window))

    @pl.when(j == nk - 1)
    def _():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, sm_scale, causal, has_bias, has_bias2, dyn_off,
                    q_off0, k_off0, window, window_span=None,
                    n_q_blocks=None, has_hg=False):
    """Grid ``(b, h_kv, ki, hg, qi)`` under GQA: group member ``hg`` (one
    of the ``H/H_kv`` query heads sharing this KV head) sweeps OUTSIDE the
    qi loop, so the (b, h_kv, ki) dk/dv output blocks are revisited only
    on consecutive steps (resident scratch accumulation over qi AND hg),
    while the per-q-head db block flushes each time its qi sweep ends.
    Plain MHA (``has_hg=False``) drops the hg grid dim entirely — grid
    ``(b, h, ki, qi)`` — r4: a singleton grid dim is not free on Mosaic's
    pipeline, and the hg predicates fold away statically."""
    kb_ref, b2_ref, qoff_ref, koff_ref, rest = _opt_refs(
        refs, has_bias, has_bias2, dyn_off)
    if has_bias:
        dk_ref, dv_ref, db_ref, dk_scr, dv_scr, db_scr = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
        db_ref = db_scr = None
    if has_hg:
        j = pl.program_id(4)
        nq = pl.num_programs(4)
        hg = pl.program_id(3)
        ng = pl.num_programs(3)
        first_sweep = jnp.logical_and(j == 0, hg == 0)
        last_sweep = lambda: jnp.logical_and(j == nq - 1, hg == ng - 1)
    else:
        j = pl.program_id(3)
        nq = pl.num_programs(3)
        first_sweep = j == 0
        last_sweep = lambda: j == nq - 1
    ki = pl.program_id(2)
    qi = j if window_span is None else ki + j
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(first_sweep)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if has_bias:
        @pl.when(j == 0)
        def _():
            db_scr[:] = jnp.zeros_like(db_scr)

    q_off, k_off, run = _offsets_and_predicates(
        qi, ki, bq, bk, causal=causal, dyn_off=dyn_off, qoff_ref=qoff_ref,
        koff_ref=koff_ref, q_off0=q_off0, k_off0=k_off0, window=window,
        window_span=window_span)
    if causal and window_span is not None:
        run = jnp.logical_and(run, qi <= n_q_blocks - 1)

    def body(mask):
        p, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, kb_ref, b2_ref, mask,
                                sm_scale=sm_scale, has_bias=has_bias,
                                has_bias2=has_bias2)
        do = do_ref[0, 0]
        # K-major outputs via leading-dim contraction — no transposes.
        dv_scr[:] = dv_scr[:] + _mm(p.astype(do.dtype), do,
                                    ((0,), (0,)))            # [bk, d]
        dk_scr[:] = dk_scr[:] + _mm(ds.astype(q_ref.dtype), q_ref[0, 0],
                                    ((0,), (0,)))            # [bk, d]
        if has_bias:
            # d(loss)/d(bias) column-sum: ds carries an extra sm_scale
            # factor (it is dL/ds * sm_scale for the dq/dk matmuls), which
            # the caller divides back out.
            db_scr[:] = db_scr[:] + jnp.sum(ds, axis=0, keepdims=True)

    _masked_split(run, body,
                  lambda: _causal_block_mask(qi, ki, bq, bk, q_off, k_off,
                                             window))

    @pl.when(last_sweep())
    def _():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)

    if has_bias:
        @pl.when(j == nq - 1)
        def _():
            db_ref[0, 0] = db_scr[:]


def _bwd_db2_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, sm_scale, causal, has_bias, dyn_off, q_off0,
                    k_off0, window, window_span=None):
    """d(loss)/d(qk_bias) summed over heads.  Separate kernel with the
    HEAD axis innermost in the grid: the (b, qi, ki) output block is then
    revisited on consecutive grid steps only, so the VMEM scratch
    accumulates across heads and flushes once — Pallas TPU does not
    re-fetch an output window revisited non-consecutively, which rules out
    accumulating this in the dkv kernel (whose grid has h outermost)."""
    kb_ref, b2_ref, qoff_ref, koff_ref, rest = _opt_refs(
        refs, has_bias, True, dyn_off)
    db2_ref, db2_scr = rest
    hi = pl.program_id(3)
    nh = pl.num_programs(3)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    ki = j if window_span is None else qi - (window_span - 1) + j
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(hi == 0)
    def _():
        db2_scr[:] = jnp.zeros_like(db2_scr)

    q_off, k_off, run = _offsets_and_predicates(
        qi, ki, bq, bk, causal=causal, dyn_off=dyn_off, qoff_ref=qoff_ref,
        koff_ref=koff_ref, q_off0=q_off0, k_off0=k_off0, window=window,
        window_span=window_span)

    def body(mask):
        _, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, kb_ref, b2_ref, mask,
                                sm_scale=sm_scale, has_bias=has_bias,
                                has_bias2=True)
        db2_scr[:] = db2_scr[:] + ds

    _masked_split(run, body,
                  lambda: _causal_block_mask(qi, ki, bq, bk, q_off, k_off,
                                             window))

    @pl.when(hi == nh - 1)
    def _():
        # ds carries the sm_scale factor used by the dq/dk matmuls;
        # divide it back out for the bias gradient.
        db2_ref[0] = db2_scr[:] * (1.0 / sm_scale)


def _flash_bwd_pallas(q, k, v, kbias, out, lse, do, *, sm_scale, causal,
                      block_q, block_k, q_offset=0, k_offset=0,
                      delta=None, qk_bias=None, window=None,
                      interpret=False):
    b, h, tq, d = q.shape
    h_kv = k.shape[1]
    grp = h // h_kv                      # query heads per KV head (GQA)
    tk = k.shape[2]
    nq, nk = tq // block_q, tk // block_k
    has_bias = kbias is not None
    has_bias2 = qk_bias is not None
    dyn_off, q_off0, k_off0 = _static_offsets(causal, q_offset, k_offset)

    if delta is None:
        # delta = rowsum(do * out) — a cheap fused reduction outside the
        # kernels; ring attention passes it in precomputed (do/out are
        # step-invariant there, so per-step recompute would be waste
        # inside the scan).
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)              # [B, H, Tq, 1]

    span = _window_span(window, block_q, block_k, q_offset, k_offset, nk)
    if span is None:
        _kc = lambda qi, j: j                      # real == grid index
        _qc = lambda ki, j: j
    else:
        _kc = lambda qi, j: jnp.maximum(qi - (span - 1) + j, 0)
        _qc = lambda ki, j: jnp.minimum(ki + j, nq - 1)
    _hk = (lambda h: h) if grp == 1 else (lambda h: h // grp)

    # Conditional operand assembly (r4): the plain causal path ships no
    # bias dummies and no offset scalars.  vma-aligned as in the fwd.
    ins = [q, k, v, do, lse, delta]
    if has_bias:
        ins.append(kbias[:, None, :])
    if has_bias2:
        ins.append(qk_bias)
    if dyn_off:
        ins += [_off_arg(q_offset), _off_arg(k_offset)]
    ins = list(_align_vma(*ins))
    q, k, v = ins[0], ins[1], ins[2]

    def specs(gridargs_to_bqk):
        """Build the common in_specs; ``gridargs_to_bqk`` maps this
        kernel's grid indices to ``(b, qi, ki, h)``."""
        def ix(f):
            return lambda *g: f(*gridargs_to_bqk(*g))
        qix = ix(lambda b, qi, ki, h: (b, h, qi, 0))
        kix = ix(lambda b, qi, ki, h: (b, _hk(h), ki, 0))     # GQA share
        rix = qix
        out = [
            pl.BlockSpec((1, 1, block_q, d), qix),
            pl.BlockSpec((1, 1, block_k, d), kix),
            pl.BlockSpec((1, 1, block_k, d), kix),
            pl.BlockSpec((1, 1, block_q, d), qix),
            pl.BlockSpec((1, 1, block_q, 1), rix),
            pl.BlockSpec((1, 1, block_q, 1), rix),
        ]
        if has_bias:
            out.append(pl.BlockSpec(
                (1, 1, block_k), ix(lambda b, qi, ki, h: (b, 0, ki))))
        if has_bias2:
            out.append(pl.BlockSpec(
                (1, block_q, block_k), ix(lambda b, qi, ki, h: (b, qi, ki))))
        if dyn_off:
            out += [_off_spec(), _off_spec()]
        return out, qix, kix

    flags = dict(sm_scale=sm_scale, causal=causal, has_bias=has_bias,
                 dyn_off=dyn_off, q_off0=q_off0, k_off0=k_off0,
                 window=window)
    in_specs, qix, _ = specs(lambda b, h, qi, j: (b, qi, _kc(qi, j), h))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, has_bias2=has_bias2,
                          window_span=span, **flags),
        grid=(b, h, nq, span if span is not None else nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d), qix),
        out_shape=_sds((b, h, tq, d), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(*ins)

    # dkv grid: (b, h_kv, ki, hg, qi) under GQA — the hg dim walks the grp
    # query heads sharing each KV head; plain MHA drops the singleton hg
    # dim entirely (r4, see kernel doc).
    has_hg = grp > 1
    if has_hg:
        in_specs, _, kix = specs(
            lambda b, hk, ki, hg, j: (b, _qc(ki, j), ki, hk * grp + hg))
        dkv_grid = (b, h_kv, nk, grp, span if span is not None else nq)
        db_ix = lambda b, hk, ki, hg, j: (b, hk * grp + hg, 0, ki)
    else:
        in_specs, _, kix = specs(
            lambda b, hk, ki, j: (b, _qc(ki, j), ki, hk))
        dkv_grid = (b, h_kv, nk, span if span is not None else nq)
        db_ix = lambda b, hk, ki, j: (b, hk, 0, ki)
    out_specs = [pl.BlockSpec((1, 1, block_k, d), kix),
                 pl.BlockSpec((1, 1, block_k, d), kix)]
    out_shape = [_sds((b, h_kv, tk, d), k.dtype, q, k, v, do),
                 _sds((b, h_kv, tk, d), v.dtype, q, k, v, do)]
    scratch = [pltpu.VMEM((block_k, d), jnp.float32),
               pltpu.VMEM((block_k, d), jnp.float32)]
    if has_bias:
        # Per-(batch, q-head) bias-gradient partials; summed over heads
        # (and un-scaled) by the caller.
        out_specs.append(pl.BlockSpec((1, 1, 1, block_k), db_ix))
        out_shape.append(_sds((b, h, 1, tk), jnp.float32, q, k, v, do))
        scratch.append(pltpu.VMEM((1, block_k), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, has_bias2=has_bias2,
                          window_span=span, n_q_blocks=nq, has_hg=has_hg,
                          **flags),
        grid=dkv_grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*ins)
    if has_bias:
        dk, dv, db_part = outs
        dbias = (jnp.sum(db_part[:, :, 0, :], axis=1)
                 / sm_scale).astype(kbias.dtype)             # [B, S]
    else:
        dk, dv = outs
        dbias = None

    dbias2 = None
    if has_bias2:
        # db2 ALWAYS uses the full masked grid: its output is the dense
        # [B, Tq, Tk] bias gradient, and out-of-band blocks must be
        # WRITTEN (as zeros) — a bounded grid would leave them undefined.
        in_specs, _, _ = specs(lambda b, qi, ki, h: (b, qi, ki, h))
        dbias2 = pl.pallas_call(
            functools.partial(_bwd_db2_kernel, window_span=None, **flags),
            grid=(b, nq, nk, h),
            in_specs=in_specs,            # h INNERMOST — see kernel doc
            out_specs=pl.BlockSpec((1, block_q, block_k),
                                   lambda b, qi, ki, h: (b, qi, ki)),
            out_shape=_sds((b, tq, tk), jnp.float32, q, k, v, do),
            scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
            interpret=interpret,
        )(*ins)
        dbias2 = dbias2.astype(qk_bias.dtype)
    return dq, dk, dv, dbias, dbias2


# -- custom VJP over the head-major layout -------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, kbias, qkbias, sm_scale, causal, window, block_q,
           block_k, interpret, q_offset):
    out, _ = _flash_fwd_pallas(q, k, v, kbias, qk_bias=qkbias,
                               sm_scale=sm_scale, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, q_offset=q_offset,
                               interpret=interpret)
    return out


def _flash_fwd_rule(q, k, v, kbias, qkbias, sm_scale, causal, window,
                    block_q, block_k, interpret, q_offset):
    out, lse = _flash_fwd_pallas(q, k, v, kbias, qk_bias=qkbias,
                                 sm_scale=sm_scale, causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k, q_offset=q_offset,
                                 interpret=interpret)
    return out, (q, k, v, kbias, qkbias, out, lse)


def _flash_bwd_rule(sm_scale, causal, window, block_q, block_k, interpret,
                    q_offset, res, do):
    q, k, v, kbias, qkbias, out, lse = res
    dq, dk, dv, dbias, dbias2 = _flash_bwd_pallas(
        q, k, v, kbias, out, lse, do, sm_scale=sm_scale, causal=causal,
        window=window, block_q=block_q, block_k=block_k, qk_bias=qkbias,
        q_offset=q_offset, interpret=interpret)
    return dq, dk, dv, dbias, dbias2


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# -- public API ----------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    key_padding_bias=None,
                    bias=None,
                    window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False):
    """Flash attention.  ``q``: [batch, q_len, heads, head_dim]; ``k,v``:
    [batch, kv_len, kv_heads, head_dim] (the JAX convention of
    ``apex_tpu.ops.attention``); returns q's shape.

    ``kv_heads`` may divide ``heads`` (grouped-query / multi-query
    attention, r3): each KV head serves ``heads / kv_heads`` query heads
    through the kernel's BlockSpec index maps — KV is never repeated or
    materialized per query head, so GQA's KV-cache/bandwidth saving is
    real on the kernel path.  The jnp fallback repeats KV heads instead
    (correct, not bandwidth-saving).
    ``key_padding_bias``: optional additive bias [batch, kv_len] applied to
    every query row (use ``0`` for visible, large-negative for padded keys).
    ``bias``: optional additive bias [batch, q_len, kv_len] broadcast over
    heads — segment masks, 2-D padding masks, relative-position biases
    (r3, VERDICT r2 weak #4).  Differentiable; its gradient (head-summed)
    is computed by a dedicated kernel pass, so only pass a learnable bias
    when you need the grad.  A per-head [B, H, T, S] bias is accepted but
    ALWAYS takes the jnp path (no kernel support).  With a [B,T,S] bias
    the DEFAULT block sizes are capped at 512 (VMEM budget for the extra
    fp32 bias blocks); an explicitly passed block_q/block_k is honored.
    ``window``: sliding-window local attention (mistral/longformer style,
    requires ``causal=True``) — each query sees the last ``window`` keys,
    itself included; out-of-band KV blocks are skipped entirely, so the
    kernel costs O(T * window) instead of O(T^2).
    On TPU (or with ``interpret=True``) runs the Pallas
    kernels; otherwise — or when the sequence doesn't tile — falls back to
    the jnp blockwise path, which computes the same function.

    **Decode-shaped inputs** (ISSUE 11 satellite): ``causal=True`` with
    ``q_len < kv_len`` treats the queries as the SUFFIX of the key
    sequence — query row ``i`` sits at global position
    ``kv_len - q_len + i`` — the KV-cache decode convention (a q_len=1
    call is one fresh token attending every cached key).  A q_len of 1
    (or any length below the kernel block size) dispatches to the
    correctly-masked jnp path; mask dead cache tail entries with
    ``key_padding_bias``.  ``q_len > kv_len`` under causal raises.
    """
    tq, tk = q.shape[1], k.shape[1]
    d = q.shape[-1]
    n_heads, n_kv = q.shape[2], k.shape[2]
    if n_heads % n_kv or v.shape[2] != n_kv:
        raise ValueError(
            f"kv heads must divide query heads and match between k and v; "
            f"got q heads {n_heads}, k heads {n_kv}, v heads {v.shape[2]}")
    # Decode-shaped causal inputs (ISSUE 11 satellite): with fewer
    # queries than keys, the queries are the SUFFIX of the sequence —
    # the last tq positions (the KV-cache decode convention: one fresh
    # token attending a cache of tk past keys).  Before this fix the
    # masked paths treated query row 0 as global position 0, so a
    # causal q_len=1 call silently attended only key 0.  Suffix
    # alignment makes causal+cross-length a correct masked path on
    # BOTH the kernel and jnp routes (q_offset is a static int, so the
    # kernels bake it as a constant — no SMEM operands).
    q_offset = 0
    if causal and tq != tk:
        if tq > tk:
            raise ValueError(
                f"causal attention needs q_len <= kv_len (queries are "
                f"the suffix of the key sequence); got q_len {tq} > "
                f"kv_len {tk}")
        q_offset = tk - tq
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding-window "
                             "local attention is causal)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = d ** -0.5
    # Plain Python flags for the tune-cache bucket, computed BEFORE the
    # bias is broadcast/folded below (a per-head [B,H,T,S] bias forces
    # the jnp path, so the consult never sees the distinction).
    tune_has_bias = bias is not None
    tune_windowed = window is not None
    per_head_bias = None
    if bias is not None and bias.ndim == 4:
        # [B, H, T, S] per-head bias: no kernel support — documented jnp
        # fallback below.
        per_head_bias, bias = bias, None
    elif bias is not None and bias.ndim == 3:
        want = (q.shape[0], tq, tk)
        if tuple(bias.shape) != want:
            # [B,1,S]-style broadcastable biases must be materialized: the
            # kernel BlockSpec indexes (b, qi, ki) into the full array and
            # would silently read clamped garbage otherwise.  broadcast_to
            # is transposed to a sum by autodiff, so dbias keeps the
            # caller's shape.
            try:
                bias = jnp.broadcast_to(bias, want)
            except ValueError:
                raise ValueError(
                    f"bias shape {bias.shape} is not broadcastable to "
                    f"[batch, q_len, kv_len] = {want}") from None
    elif bias is not None:
        raise ValueError(
            f"bias must be [batch, q_len, kv_len] (broadcast over heads) "
            f"or per-head [batch, heads, q_len, kv_len]; got {bias.shape}")
    if bias is not None and key_padding_bias is not None:
        # one additive term covers both: fold the key bias in
        bias = bias + key_padding_bias[:, None, :].astype(bias.dtype)
        key_padding_bias = None

    # None sentinels distinguish "caller did not pass blocks" from a
    # caller explicitly passing the default values (code-review r5): the
    # shape dispatch and the bias cap apply ONLY to un-passed defaults.
    defaults_used = block_q is None and block_k is None
    if block_q is None:
        block_q = _DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = _DEFAULT_BLOCK_K
    if bias is not None:
        # The [B,T,S] bias path moves an extra (block_q, block_k) fp32
        # block per grid step in BOTH directions (b2 input fwd/bwd, db2
        # output + scratch) — at the 1024^2 default that is several more
        # 4 MB VMEM residents the r4 block sweep (bias-free) never
        # budgeted.  Cap the bias path at the r3-proven 512^2 — but only
        # when the caller left the defaults; an explicit block_q/block_k
        # is honored as given (ADVICE r4: callers who measured a larger
        # block fitting must be able to opt in).
        if defaults_used:
            block_q = min(block_q, 512)
            block_k = min(block_k, 512)
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    vma_live = False       # under shard_map vma tracking, interpret-mode
    for x in (q, k, v, bias, key_padding_bias):   # emulation cannot run the
        try:               # kernels (the hlo-interpreter block loops index
            vma_live |= bool(jax.typeof(x).vma)   # varying operands with
        except (AttributeError, TypeError):       # unvarying iotas)
            pass                                  # None / vma-less avals
    use_kernel = ((interpret or _use_pallas()) and bq is not None
                  and bk is not None and pltpu is not None
                  and not (interpret and vma_live)
                  and per_head_bias is None
                  and not (not interpret
                           and _dispatch_to_jnp(tq, tk, defaults_used)))
    if not use_kernel:
        from .attention import blockwise_attention
        b4 = per_head_bias
        if key_padding_bias is not None:
            kb4 = key_padding_bias[:, None, None, :]
            b4 = kb4 if b4 is None else b4 + kb4.astype(b4.dtype)
        if bias is not None:
            b4 = bias[:, None, :, :]
        if n_kv != n_heads:      # GQA off the kernel path: repeat KV heads
            k = jnp.repeat(k, n_heads // n_kv, axis=2)
            v = jnp.repeat(v, n_heads // n_kv, axis=2)
        if window is not None:   # sliding window as an additive band bias
            wb = jnp.where(
                ((q_offset + jnp.arange(tq))[:, None]
                 - jnp.arange(tk)[None, :]) < window,
                0.0, NEG_INF).astype(jnp.float32)
            b4 = wb[None, None] if b4 is None else b4 + wb[None, None]
        # Shape-dispatched short-seq case: one whole-array block (the
        # [T,S] scores fit comfortably below the crossover) — a scan over
        # 512-blocks would only add online-softmax carry overhead here.
        bs = tk if tk < _KERNEL_MIN_KV else 512
        return blockwise_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   bias=b4, block_size=bs,
                                   q_offset=q_offset)

    # Dispatch-time autotune consult (ISSUE 14): when the caller left
    # the blocks at their defaults and the kernel path won, the
    # per-device config cache may override the hand-picked v5e sweep
    # constants.  A tuned block that does not tile this exact sequence
    # (cache written from a different length in the same pow2 bucket)
    # falls back to the defaults already computed above.  Explicit
    # block_q/block_k callers — and the jnp path — never consult.
    if defaults_used:
        cfg = _tuned_config(
            "flash_attention", TUNE_VERSION,
            tune_bucket(tq, tk, d, causal, tune_has_bias, tune_windowed),
            params=("block_q", "block_k"))
        if cfg:
            tbq = _pick_block(tq, cfg["block_q"])
            tbk = _pick_block(tk, cfg["block_k"])
            if tbq is not None and tbk is not None:
                bq, bk = tbq, tbk

    qt = q.transpose(0, 2, 1, 3)                         # [B, H, T, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kb = (None if key_padding_bias is None
          else key_padding_bias.astype(jnp.float32))
    # bias keeps its own dtype ([B,T,S] is quadratic; an eager fp32 copy
    # would double its HBM footprint) — the kernels widen each block.
    out = _flash(qt, kt, vt, kb, bias, float(sm_scale), bool(causal),
                 None if window is None else int(window),
                 int(bq), int(bk), bool(interpret), int(q_offset))
    return out.transpose(0, 2, 1, 3)
