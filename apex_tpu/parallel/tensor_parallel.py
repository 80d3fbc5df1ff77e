"""Tensor (model) parallelism over a mesh axis — Megatron-style split.

Beyond-parity scope (the reference implements data parallelism only,
SURVEY.md §2.10); on TPU tensor parallelism is the natural second mesh
axis, riding ICI with one ``psum`` per row-parallel matmul.

The canonical pattern (used by the dryrun's dp × tp phase and the tests):

* **column-parallel** weight ``[d_in, d_out/ntp]`` per shard — output is
  feature-sharded, NO collective (the gather is deferred);
* **row-parallel** weight ``[d_in/ntp, d_out]`` per shard — consumes the
  feature-sharded activation and ``psum``s the partial products over the
  tp axis.

A column→row pair (the transformer MLP / attention-out shape) therefore
costs exactly one all-reduce, and weight gradients stay local to each
shard — the dp gradient reduction must run over the *data* axis only for
these params (``reduce_gradients(axis_name="data")``), which is why they
live in a separate pytree subtree by convention.

Use inside ``shard_map`` with the weights' ``PartitionSpec`` carrying the
tp axis on the split dimension::

    mesh = Mesh(devices.reshape(dp, tp), ("data", "tp"))
    in_specs = (P(), {"w1": P(None, "tp"), "w2": P("tp", None)}, ...)

Sharded-parameter *initialization* helpers are provided so a replicated
fp32 master checkpoint maps deterministically onto shards
(``shard_column`` / ``shard_row``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax



def _axis_index(axis_name):
    return lax.axis_index(axis_name)


def column_parallel_dense(x, w_local, b_local=None):
    """``y_local = x @ w_local (+ b_local)`` — output feature-sharded.

    ``w_local``: this shard's ``[d_in, d_out/ntp]`` slice, ``b_local`` the
    matching bias slice.  No collective.
    """
    y = jnp.dot(x, w_local.astype(x.dtype))
    if b_local is not None:
        y = y + b_local.astype(y.dtype)
    return y


def row_parallel_dense(x_local, w_local, axis_name: str, b=None):
    """``y = psum_tp(x_local @ w_local) (+ b)`` — the one collective of a
    column→row pair.

    ``x_local``: feature-sharded activation ``[..., d_in/ntp]``;
    ``w_local``: this shard's ``[d_in/ntp, d_out]`` slice; ``b`` is the
    full (replicated) bias, added AFTER the reduction so it isn't summed
    ntp times.
    """
    partial = jnp.dot(x_local, w_local.astype(x_local.dtype))
    y = lax.psum(partial, axis_name)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def tp_mlp(x, w1_local, b1_local, w2_local, b2, axis_name: str,
           activation=jax.nn.gelu):
    """Megatron MLP: column-parallel up-proj, activation, row-parallel
    down-proj — one psum total."""
    h = column_parallel_dense(x, w1_local, b1_local)
    h = activation(h.astype(jnp.float32)).astype(x.dtype)
    return row_parallel_dense(h, w2_local, axis_name, b=b2)


def tp_self_attention(x, wqkv_local, wo_local, num_heads_local: int,
                      axis_name: str, causal: bool = False,
                      attention_fn=None):
    """Head-parallel self-attention: each tp shard owns
    ``num_heads/ntp`` heads end-to-end; the output projection is
    row-parallel (one psum).

    ``wqkv_local``: ``[d, 3, heads_local, head_dim]``;
    ``wo_local``: ``[heads_local * head_dim, d]``.

    The default ``attention_fn`` is :func:`~apex_tpu.ops.flash_attention.
    flash_attention` (r3, VERDICT r2 weak #3): on TPU the tp shard's local
    heads run the Pallas flash kernel (which traces under shard_map's
    default vma tracking since the operand alignment fix); off-TPU or on
    non-tiling shapes it degrades to the same jnp blockwise math it used
    before, so the change is pure speedup.
    """
    if wqkv_local.shape[2] != num_heads_local:
        raise ValueError(
            f"num_heads_local={num_heads_local} does not match "
            f"wqkv_local's head dim {wqkv_local.shape[2]} — pass this "
            f"shard's head count (global heads / tp axis size)")
    b, t, d = x.shape
    qkv = jnp.einsum("btd,dche->btche", x, wqkv_local.astype(x.dtype))
    q, k, v = (qkv[:, :, i] for i in range(3))    # each [b, t, h_local, e]
    if attention_fn is None:
        from ..ops.flash_attention import flash_attention
        attention_fn = lambda q, k, v: flash_attention(q, k, v,
                                                       causal=causal)
    ctx = attention_fn(q, k, v)                       # [b, t, h_local, hd]
    ctx = ctx.reshape(b, t, -1)
    return row_parallel_dense(ctx, wo_local, axis_name)


# -- checkpoint <-> shard mapping ---------------------------------------------

def shard_column(w, axis_name: str, n: Optional[int] = None):
    """Slice a replicated ``[d_in, d_out]`` weight to this shard's
    column-parallel ``[d_in, d_out/n]`` piece (inside shard_map)."""
    n = n or lax.axis_size(axis_name)
    if w.shape[-1] % n:
        raise ValueError(
            f"column-parallel split needs d_out {w.shape[-1]} divisible by "
            f"the tp axis size {n} — trailing columns would be dropped")
    cols = w.shape[-1] // n
    return lax.dynamic_slice_in_dim(w, _axis_index(axis_name) * cols, cols,
                                    axis=w.ndim - 1)


def shard_row(w, axis_name: str, n: Optional[int] = None):
    """Slice a replicated ``[d_in, d_out]`` weight to this shard's
    row-parallel ``[d_in/n, d_out]`` piece (inside shard_map)."""
    n = n or lax.axis_size(axis_name)
    if w.shape[0] % n:
        raise ValueError(
            f"row-parallel split needs d_in {w.shape[0]} divisible by "
            f"the tp axis size {n} — trailing rows would be dropped")
    rows = w.shape[0] // n
    return lax.dynamic_slice_in_dim(w, _axis_index(axis_name) * rows, rows,
                                    axis=0)
