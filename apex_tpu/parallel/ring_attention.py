"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Beyond-parity scope (the reference has no attention or sequence parallelism
— SURVEY.md §2.10/§5); first-class here because long-context training is a
core TPU workload and shapes the mesh design.

Two strategies over a mesh axis ``sp`` holding sequence shards:

* **Ring attention** (:func:`ring_attention`) — Q stays resident; KV shards
  rotate around the ring via ``lax.ppermute`` while each device accumulates
  the online-softmax recurrence (``ops.attention.attention_block_update``).
  Communication rides ICI neighbor links (a ``ppermute`` ring), overlapping
  with the per-block matmuls; memory is O(T/n) per device.  Causal masking
  uses each block's global offsets, so rotated blocks mask correctly.

* **Ulysses** (:func:`ulysses_attention`) — two ``all_to_all``s re-shard
  from sequence-sharded to head-sharded, run *local* full attention, and
  shard back.  Cheaper at moderate T (2 collectives instead of n-1
  permutes) but caps parallelism at num_heads.

Both are pure functions designed for use inside ``shard_map`` and agree
with single-device blockwise attention to numerical precision (see
tests/test_ring_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (attention_block_update, _init_carry,
                             finalize_attention, blockwise_attention)


def ring_attention(q, k, v, axis_name: str, *,
                   causal: bool = False,
                   sm_scale: Optional[float] = None,
                   block_size: int = 512):
    """Ring attention over sequence shards (inside shard_map).

    ``q``/``k``/``v``: local shards [B, T/n, H, D] where the global sequence
    is split contiguously over ``axis_name`` in rank order.  Returns the
    local output shard [B, T/n, H, D].
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    q_offset = idx * t_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Sub-block the local shard when it exceeds block_size, bounding the
    # per-step score matrix at [B,H,T/n,block_size].
    blk = min(block_size, t_local)
    n_sub = t_local // blk
    rem = t_local - n_sub * blk

    def _consume_shard(kb, vb, m, l, acc, k_offset):
        if n_sub <= 1 and rem == 0:
            return attention_block_update(
                q, kb, vb, m, l, acc, sm_scale=sm_scale, causal=causal,
                q_offset=q_offset, k_offset=k_offset)
        for s in range(n_sub):
            m, l, acc = attention_block_update(
                q, kb[:, s * blk:(s + 1) * blk], vb[:, s * blk:(s + 1) * blk],
                m, l, acc, sm_scale=sm_scale, causal=causal,
                q_offset=q_offset, k_offset=k_offset + s * blk)
        if rem:
            m, l, acc = attention_block_update(
                q, kb[:, -rem:], vb[:, -rem:], m, l, acc,
                sm_scale=sm_scale, causal=causal, q_offset=q_offset,
                k_offset=k_offset + n_sub * blk)
        return m, l, acc

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def step(carry, r):
        kv, m, l, acc = carry
        kb, vb = kv
        # This KV block originated at rank (idx - r) mod n.
        k_offset = ((idx - r) % n) * t_local
        m, l, acc = _consume_shard(kb, vb, m, l, acc, k_offset)
        # Rotate for the next step (skipped result on the last iteration
        # costs nothing: XLA dead-code-eliminates... but ppermute is a
        # collective every rank must execute, so keep it unconditional).
        kv = jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis_name, perm), kv)
        return (kv, m, l, acc), None

    m0, l0, acc0 = _init_carry(b, t_local, h, d)
    # The zeros carry is axis-unvarying but the body produces values varying
    # over every manual axis q varies over (sp, plus e.g. data on a 2-D
    # mesh); align the vma types up front (shard_map scan requirement).
    if _vma_tracking_live(axis_name):
        target_vma = tuple(jax.typeof(q).vma | {axis_name})
        m0, l0, acc0 = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, target_vma, to="varying"), (m0, l0, acc0))
    (_, m, l, acc), _ = lax.scan(step, ((k, v), m0, l0, acc0),
                                 jnp.arange(n))
    return finalize_attention(m, l, acc, q.dtype)


from .distributed import vma_tracking_live as _vma_tracking_live


def _logaddexp(a, b):
    m = jnp.maximum(a, b)
    return m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m))


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale, block_q,
                         block_k, interpret):
    """Forward: per-shard Pallas flash partials (normalized out_i + lse_i)
    merged across ring steps by logsumexp weights.  Head-major in/out."""
    from ..ops.flash_attention import _flash_fwd_pallas

    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    q_off = idx * t_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    lse0 = jnp.full((b, h, t_local, 1), -1e30, jnp.float32)
    out0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    if _vma_tracking_live(axis_name):
        target_vma = tuple(jax.typeof(q).vma | {axis_name})
        lse0, out0 = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, target_vma, to="varying"), (lse0, out0))

    def step(carry, r):
        (kc, vc), lse_run, out_run = carry
        j = (idx - r) % n
        out_i, lse_i = _flash_fwd_pallas(
            q, kc, vc, None, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k,
            q_offset=q_off, k_offset=j * t_local, interpret=interpret)
        new_lse = _logaddexp(lse_run, lse_i)
        out_run = (out_run * jnp.exp(lse_run - new_lse)
                   + out_i.astype(jnp.float32) * jnp.exp(lse_i - new_lse))
        kc, vc = jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis_name, perm), (kc, vc))
        return ((kc, vc), new_lse, out_run), None

    (_, lse, out), _ = lax.scan(step, ((k, v), lse0, out0), jnp.arange(n))
    return out.astype(q.dtype), lse


def _ring_flash_bwd_impl(q, k, v, out, lse, do, axis_name, causal, sm_scale,
                         block_q, block_k, interpret):
    """Backward: re-rotate KV; per shard run the flash backward kernels
    with the GLOBAL lse (so recomputed p are the true global softmax
    probabilities); dq accumulates locally, dk/dv accumulate in buffers
    that rotate WITH their kv shard and arrive home after the full cycle."""
    from ..ops.flash_attention import _flash_bwd_pallas

    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, t_local, d = q.shape
    q_off = idx * t_local
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    dk0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    dv0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    if _vma_tracking_live(axis_name):
        target_vma = tuple(jax.typeof(q).vma | {axis_name})
        dq0, dk0, dv0 = jax.tree_util.tree_map(
            lambda x: lax.pcast(x, target_vma, to="varying"), (dq0, dk0, dv0))

    # do/out are step-invariant: compute delta once, outside the scan.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def step(carry, r):
        (kc, vc, dkc, dvc), dq = carry
        j = (idx - r) % n
        dq_i, dk_i, dv_i, _, _ = _flash_bwd_pallas(
            q, kc, vc, None, out, lse, do, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k,
            q_offset=q_off, k_offset=j * t_local, delta=delta,
            interpret=interpret)
        dq = dq + dq_i.astype(jnp.float32)
        dkc = dkc + dk_i.astype(jnp.float32)
        dvc = dvc + dv_i.astype(jnp.float32)
        kc, vc, dkc, dvc = jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis_name, perm), (kc, vc, dkc, dvc))
        return ((kc, vc, dkc, dvc), dq), None

    ((_, _, dk, dv), dq), _ = lax.scan(
        step, ((k, v, dk0, dv0), dq0), jnp.arange(n))
    # n rotations = identity: dk/dv are home with every rank's contribution.
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
                interpret):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale,
                                  block_q, block_k, interpret)
    return out


def _ring_flash_fwd_rule(q, k, v, axis_name, causal, sm_scale, block_q,
                         block_k, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, sm_scale,
                                    block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_rule(axis_name, causal, sm_scale, block_q, block_k,
                         interpret, res, do):
    q, k, v, out, lse = res
    return _ring_flash_bwd_impl(q, k, v, out, lse, do, axis_name, causal,
                                sm_scale, block_q, block_k, interpret)


_ring_flash.defvjp(_ring_flash_fwd_rule, _ring_flash_bwd_rule)


def ring_flash_attention(q, k, v, axis_name: str, *,
                         causal: bool = False,
                         sm_scale: Optional[float] = None,
                         block_q: int = 512,
                         block_k: int = 512,
                         interpret: bool = False):
    """Ring attention with the Pallas flash kernels as the local op.

    Same contract as :func:`ring_attention` (call inside shard_map with
    contiguous sequence shards [B, T/n, H, D] over ``axis_name``) but each
    ring step runs the MXU flash kernel and saves only one fp32 logsumexp
    per row; the backward re-rotates KV and runs the flash backward
    kernels against the *global* lse, so gradients are exact.  Falls back
    to the jnp :func:`ring_attention` off-TPU or when the shard length
    doesn't block-align.  Runs under ``shard_map``'s DEFAULT vma tracking
    (r3: the kernels pcast-align their rank-varying offset operands —
    ``pallas_compat.align_vma`` — so ``check_vma=False`` is no longer
    required for the Mosaic fast path; only ``interpret=True`` emulation
    still needs the jnp route there, a jax hlo-interpreter limitation —
    its internal block loops index varying operands with unvarying iotas).
    """
    from ..ops.flash_attention import _pick_block, _use_pallas, pltpu

    t_local, d = q.shape[1], q.shape[3]
    if sm_scale is None:
        sm_scale = d ** -0.5
    bq = _pick_block(t_local, block_q)
    bk = _pick_block(t_local, block_k)
    use_kernel = ((interpret or _use_pallas()) and bq is not None
                  and bk is not None and pltpu is not None
                  and not (interpret and _vma_tracking_live(axis_name)))
    if not use_kernel:
        return ring_attention(q, k, v, axis_name, causal=causal,
                              sm_scale=sm_scale)
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _ring_flash(qt, kt, vt, axis_name, bool(causal), float(sm_scale),
                      int(bq), int(bk), bool(interpret))
    return out.transpose(0, 2, 1, 3)


def ulysses_attention(q, k, v, axis_name: str, *,
                      causal: bool = False,
                      sm_scale: Optional[float] = None,
                      block_size: int = 512):
    """Ulysses-style all-to-all sequence parallelism (inside shard_map).

    Local shards [B, T/n, H, D] → all_to_all → [B, T, H/n, D] → local
    blockwise attention over the FULL sequence → all_to_all back.
    Requires ``H % n == 0``.
    """
    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"num_heads {h} not divisible by axis size {n}")

    def seq_to_heads(x):
        # split heads (axis 2) across ranks, gather sequence (axis 1)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = blockwise_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale,
                              block_size=block_size)
    return heads_to_seq(out)
