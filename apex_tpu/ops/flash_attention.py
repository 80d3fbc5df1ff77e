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
* **backward** — one kernel (``flash_bwd``, ISSUE 30) that recomputes
  ``p = exp(s - lse)`` and ``ds`` ONCE per live ``(qi, ki)`` tile and
  takes dq, dk and dv from them: five block products where a dq kernel
  and a dk/dv kernel, each rebuilding the scores, did seven.  The KV step
  is innermost (the ``[bq, d]`` dq accumulator's order); dk and dv
  accumulate in float32 scratch of the whole key length, written once per
  ``(batch, kv_head)``.  Every matmul is expressed in the natural
  ``[bq, bk]`` orientation with leading-dim contractions where the output
  is K-major, so no operand ever needs a VMEM relayout/transpose.
  ``delta = rowsum(do * o)`` is a cheap jnp reduction fused by XLA.  Keys
  beyond the resident-VMEM budget run the same kernel per KV chunk.
* causal masking skips fully-masked KV blocks via ``pl.when`` predication,
  and sliding-window local attention goes further with a BOUNDED grid:
  only ``ceil(window/bk)+1`` KV blocks per Q block are even visited
  (virtual-negative block ids clamp in the index maps and predicate off),
  so local attention is O(T * window) in both compute and fetches;
  a key-side additive bias ``[batch, kv_len]`` covers padding masks and a
  head-broadcast ``[batch, q_len, kv_len]`` bias covers segment/2-D masks
  and relative-position biases, with its head-summed gradient produced by
  a dedicated second backward kernel (grid head-innermost so the output
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
#: (ISSUE 14) — covers the forward AND the backward kernel (they share
#: block_q/block_k); bump when the grid/block semantics change.  2: the
#: fused backward (ISSUE 30).
TUNE_VERSION = 2
# Block sweep on this installation's v5e (PR 30; causal bf16, head size
# 64; device time of one call from the profiler's trace, ms, forward /
# fused backward, block_q x block_k):
#   B 8, H 12, T 1,024 (gpt2_small_o2.seq1024's layer):
#     512x512 0.600 / 0.752   512x1024 0.479 / 0.771
#     1024x512 0.784 / 0.761  1024x1024 0.334 / 0.777
#   B 2, H 32 over 8 KV heads, T 4,096 (granite4_h_micro_o2's layer):
#     512x512 4.34 / 6.04     512x1024 3.10 / 6.01
#     1024x512 5.05 / 5.70    1024x1024 2.87 / 5.64
# 256-blocks lose everywhere (host clock: backward +3 to +70%).  The
# backward is flat within 3% over {512, 1024}^2 at T 1,024 (a 512 grid
# skips the tile above the diagonal and pays four times the grid steps);
# the forward is not, and the two share their blocks through the custom
# VJP's static arguments: 1024 x 1024 wins forward + backward at both
# shapes (1.11 and 8.51 ms).
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
    the block-skip predicate shared by all three kernels.  Blocks past the
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
    # of grid rank (the backward grid is 5-D under GQA, 4-D otherwise).
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


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *refs, sm_scale, causal, has_bias, has_bias2, dyn_off,
                q_off0, k_off0, window, window_span=None, k_blk0=0,
                has_hg=False):
    """dq, dk and dv (and the key-bias gradient) from ONE recomputation
    of ``p`` and ``ds`` per live tile.

    Grid ``(b, h_kv, hg, qi, j)`` with the KV step innermost, which is
    what the ``[bq, d]`` dq accumulator wants; dk and dv accumulate in
    float32 scratch of the WHOLE key length (rows ``ki * bk`` onwards per
    tile), so their ``(1, 1, tk, d)`` output blocks stay resident while
    ``(b, h_kv)`` is fixed and are written once, after the last
    ``(hg, qi, j)`` — any visiting order of the KV blocks serves, the
    bounded sliding-window grid's included.  ``hg`` walks the ``H/H_kv``
    query heads that share this KV head; plain MHA (``has_hg=False``)
    drops the dim — grid ``(b, h, qi, j)`` — r4: a singleton grid dim is
    not free on Mosaic's pipeline.  The refs may hold one chunk of the
    keys, beginning ``k_blk0`` blocks (``k_off0`` keys) in: the bounded
    grid walks the band of the WHOLE key length, and the tiles of it that
    fall outside this chunk are dead."""
    kb_ref, b2_ref, qoff_ref, koff_ref, rest = _opt_refs(
        refs, has_bias, has_bias2, dyn_off)
    if has_bias:
        dq_ref, dk_ref, dv_ref, db_ref, dq_scr, dk_scr, dv_scr, db_scr = rest
    else:
        dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
        db_ref = db_scr = None
    ax = 3 if has_hg else 2                      # qi's grid axis
    qi, j = pl.program_id(ax), pl.program_id(ax + 1)
    nq, nj = pl.num_programs(ax), pl.num_programs(ax + 1)
    first = jnp.logical_and(qi == 0, j == 0)
    last = jnp.logical_and(qi == nq - 1, j == nj - 1)
    if has_hg:
        first = jnp.logical_and(first, pl.program_id(2) == 0)
        last = jnp.logical_and(
            last, pl.program_id(2) == pl.num_programs(2) - 1)
    # ki: this tile's KV block WITHIN the chunk
    ki = j if window_span is None else qi - (window_span - 1) + j - k_blk0
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    if dk_scr.shape[0] == bk:                    # one KV block: no offset
        rows = slice(None)
    else:
        rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)

    @pl.when(first)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if has_bias:
            db_scr[:] = jnp.zeros_like(db_scr)

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_off, k_off, run = _offsets_and_predicates(
        qi, ki, bq, bk, causal=causal, dyn_off=dyn_off, qoff_ref=qoff_ref,
        koff_ref=koff_ref, q_off0=q_off0, k_off0=k_off0, window=window,
        window_span=window_span)
    if window_span is not None:      # the band runs on past this chunk
        run = jnp.logical_and(run, ki < dk_scr.shape[0] // bk)

    def body(mask):
        p, ds = _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, kb_ref, b2_ref, mask,
                                sm_scale=sm_scale, has_bias=has_bias,
                                has_bias2=has_bias2)
        do = do_ref[0, 0]
        dq_scr[:] = dq_scr[:] + _mm(ds.astype(k_ref.dtype), k_ref[0, 0],
                                    ((1,), (0,)))
        # K-major outputs via leading-dim contraction — no transposes.
        dv_scr[rows, :] = dv_scr[rows, :] + _mm(p.astype(do.dtype), do,
                                                ((0,), (0,)))    # [bk, d]
        dk_scr[rows, :] = dk_scr[rows, :] + _mm(ds.astype(q_ref.dtype),
                                                q_ref[0, 0],
                                                ((0,), (0,)))    # [bk, d]
        if has_bias:
            # d(loss)/d(bias) column-sum: ds carries an extra sm_scale
            # factor (it is dL/ds * sm_scale for the dq/dk matmuls), which
            # the caller divides back out.
            db_scr[ki] = db_scr[ki] + jnp.sum(ds, axis=0, keepdims=True)

    _masked_split(run, body,
                  lambda: _causal_block_mask(qi, ki, bq, bk, q_off, k_off,
                                             window))

    @pl.when(j == nj - 1)
    def _():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(last)
    def _():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)
        if has_bias:
            db_ref[0, 0] = db_scr[:]


def _bwd_db2_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, sm_scale, causal, has_bias, dyn_off, q_off0,
                    k_off0, window, window_span=None):
    """d(loss)/d(qk_bias) summed over heads.  Separate kernel with the
    HEAD axis innermost in the grid: the (b, qi, ki) output block is then
    revisited on consecutive grid steps only, so the VMEM scratch
    accumulates across heads and flushes once — Pallas TPU does not
    re-fetch an output window revisited non-consecutively, which rules out
    accumulating this in ``_bwd_kernel`` (whose grid has h outermost)."""
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


def _vmem_capacity():
    """VMEM bytes of one core of the device the kernels compile for; off
    the TPU (interpret mode, a rehearsal compile for a described chip)
    there is none to ask, and the answer is a v5e's."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except Exception:
        return 128 * 2**20


def _bwd_kv_chunk(tk, d, itemsize, block_k):
    """``(keys, vmem_limit_bytes)`` of one ``flash_bwd`` call.  What a
    call holds for all of its keys is the two float32 dk/dv accumulators
    and the two double-buffered dk/dv output blocks, the head dim padded
    to whole 128-lane rows.  Keys: as many as keep that within a quarter
    of the core's VMEM (16,384 in bf16 on a v5e's 128 MiB, at head size
    64 or 128 alike), in whole KV blocks and at least one; more keys than
    that go through the same kernel one chunk at a time.  Scoped-VMEM
    limit: those residents plus 12 MiB for the recompute's ``[bq, bk]``
    tiles and the operand blocks up to 1024 x 1024 (8 MiB is short by 0.6
    at 16,384 keys; rehearsal compiles for a v5e), never under Mosaic's
    16 MiB default, which is the limit at T 1,024 (20 MiB at 4,096
    keys)."""
    cap = _vmem_capacity()
    per_block = block_k * (-(-d // 128) * 128) * (2 * 4 + 2 * 2 * itemsize)
    blocks = min(tk // block_k, max(1, cap // 4 // per_block))
    limit = max(16 * 2**20, 12 * 2**20 + blocks * per_block)
    return blocks * block_k, min(cap, limit)


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

    _hk = (lambda h: h) if grp == 1 else (lambda h: h // grp)

    def operands(k, v, kbias, qk_bias, k_offset):
        """Conditional operand assembly (r4): the plain causal path ships
        no bias dummies and no offset scalars.  vma-aligned as in the
        fwd."""
        ins = [q, k, v, do, lse, delta]
        if has_bias:
            ins.append(kbias[:, None, :])
        if has_bias2:
            ins.append(qk_bias)
        if dyn_off:
            ins += [_off_arg(q_offset), _off_arg(k_offset)]
        return list(_align_vma(*ins))

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
        return out, qix

    # Keys beyond the resident budget: the same kernel per KV chunk, dq
    # leaving each call in float32 and summed (the sum ring attention
    # makes over its steps).  One chunk is the common case: the loop runs
    # once and dq leaves the kernel in its own dtype.
    chunk, vmem_limit = _bwd_kv_chunk(tk, d, q.dtype.itemsize, block_k)
    dq_dtype = q.dtype if chunk == tk else jnp.float32
    # The bounded window grid walks `span` KV blocks of the WHOLE key
    # length per Q block; a chunk runs the tiles of that band it holds.
    span = _window_span(window, block_q, block_k, q_offset, k_offset, nk)

    def fused(start):
        """One ``flash_bwd`` call over the ``chunk`` keys from ``start``:
        (dq, dk, dv, db_part)."""
        rows = slice(start, min(start + chunk, tk))
        ins = operands(k[:, :, rows], v[:, :, rows],
                       kbias[:, rows] if has_bias else None,
                       qk_bias[:, :, rows] if has_bias2 else None,
                       k_offset + start)
        like = ins[:4]
        tkc = ins[1].shape[2]
        nkc, k_blk0 = tkc // block_k, start // block_k
        if span is None:
            nj = nkc
            _kc = lambda qi, j: j                  # real == grid index
        else:           # clamped real block for a virtual or foreign ki
            nj = span
            _kc = lambda qi, j: jnp.clip(qi - (span - 1) + j - k_blk0,
                                         0, nkc - 1)
        # grid (b, h_kv, hg, qi, j) under GQA — hg walks the grp query
        # heads sharing each KV head; plain MHA drops the singleton hg
        # dim entirely (r4, see kernel doc).
        has_hg = grp > 1
        if has_hg:
            grid = (b, h_kv, grp, nq, nj)
            in_specs, qix = specs(
                lambda b, hk, hg, qi, j: (b, qi, _kc(qi, j), hk * grp + hg))
            kvix = lambda b, hk, hg, qi, j: (b, hk, 0, 0)
            dbix = lambda b, hk, hg, qi, j: (b, hk, 0, 0, 0)
        else:
            grid = (b, h, nq, nj)
            in_specs, qix = specs(
                lambda b, h, qi, j: (b, qi, _kc(qi, j), h))
            kvix = lambda b, h, qi, j: (b, h, 0, 0)
            dbix = lambda b, h, qi, j: (b, h, 0, 0, 0)
        out_specs = [pl.BlockSpec((1, 1, block_q, d), qix),
                     pl.BlockSpec((1, 1, tkc, d), kvix),
                     pl.BlockSpec((1, 1, tkc, d), kvix)]
        out_shape = [_sds((b, h, tq, d), dq_dtype, *like),
                     _sds((b, h_kv, tkc, d), k.dtype, *like),
                     _sds((b, h_kv, tkc, d), v.dtype, *like)]
        scratch = [pltpu.VMEM((block_q, d), jnp.float32),
                   pltpu.VMEM((tkc, d), jnp.float32),
                   pltpu.VMEM((tkc, d), jnp.float32)]
        if has_bias:
            # Per-(batch, KV-head) bias-gradient partials, one row per KV
            # block; summed over heads (and un-scaled) by the caller.
            out_specs.append(pl.BlockSpec((1, 1, nkc, 1, block_k), dbix))
            out_shape.append(
                _sds((b, h_kv, nkc, 1, block_k), jnp.float32, *like))
            scratch.append(pltpu.VMEM((nkc, 1, block_k), jnp.float32))
        outs = pl.pallas_call(
            functools.partial(
                _bwd_kernel, sm_scale=sm_scale, causal=causal,
                has_bias=has_bias, has_bias2=has_bias2, dyn_off=dyn_off,
                q_off0=q_off0, k_off0=k_off0 + start, window=window,
                window_span=span, k_blk0=k_blk0, has_hg=has_hg),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            name="flash_bwd",
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=vmem_limit),
        )(*ins)
        return tuple(outs) if has_bias else (*outs, None)

    dqs, dks, dvs, dbs = zip(*(fused(s) for s in range(0, tk, chunk)))
    dq = functools.reduce(jnp.add, dqs).astype(q.dtype)
    dk = jnp.concatenate(dks, axis=2)
    dv = jnp.concatenate(dvs, axis=2)
    db_part = jnp.concatenate(dbs, axis=2) if has_bias else None
    dbias = None
    if has_bias:
        dbias = (jnp.sum(db_part.reshape(b, h_kv, tk), axis=1)
                 / sm_scale).astype(kbias.dtype)             # [B, S]

    dbias2 = None
    if has_bias2:
        # db2 ALWAYS uses the full masked grid: its output is the dense
        # [B, Tq, Tk] bias gradient, and out-of-band blocks must be
        # WRITTEN (as zeros) — a bounded grid would leave them undefined.
        in_specs, _ = specs(lambda b, qi, ki, h: (b, qi, ki, h))
        ins = operands(k, v, kbias, qk_bias, k_offset)
        dbias2 = pl.pallas_call(
            functools.partial(
                _bwd_db2_kernel, sm_scale=sm_scale, causal=causal,
                has_bias=has_bias, dyn_off=dyn_off, q_off0=q_off0,
                k_off0=k_off0, window=window, window_span=None),
            grid=(b, nq, nk, h),
            in_specs=in_specs,            # h INNERMOST — see kernel doc
            out_specs=pl.BlockSpec((1, block_q, block_k),
                                   lambda b, qi, ki, h: (b, qi, ki)),
            out_shape=_sds((b, tq, tk), jnp.float32, *ins[:4]),
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
