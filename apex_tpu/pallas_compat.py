"""Shared helpers at the Pallas kernel boundary: keeping operands,
outputs and cotangents consistent with ``shard_map``'s
varying-manual-axes (vma) tracking, and the in-kernel MXU matmul with
the precision Mosaic accepts.

Lives at the package root (not under ``ops``/``normalization``) because
both import it and ``ops`` ↔ ``normalization`` already depend on each
other through the kernel gating.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["sds_with_vma", "align_vma", "match_vma", "mxu_dot"]


def mxu_dot(a, b, dims, acc_dtype=jnp.float32):
    """In-kernel MXU matmul (``dims`` = the contracting dimensions)
    accumulating in ``acc_dtype``.  Precision must be explicit: a global
    ``jax_default_matmul_precision=highest`` (the test conftest; the
    imagenet example's ``--deterministic``) otherwise lowers bf16/int8
    operands to an fp32 contract precision that Mosaic cannot compile
    ("Mosaic failed to compile TPU kernel: Bad lhs type"); fp32 operands
    conversely need HIGHEST to match the jnp oracles instead of the
    TPU's default one-pass bf16 multiply."""
    prec = (lax.Precision.HIGHEST
            if a.dtype == jnp.float32 and b.dtype == jnp.float32
            else lax.Precision.DEFAULT)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=acc_dtype,
                           precision=prec)


def align_vma(*arrays):
    """``pcast`` every array up to the union of all the arrays' vma
    (varying-manual-axes) sets.

    ``pallas_call`` under ``shard_map``'s default ``check_vma=True``
    requires its operands to agree on how they vary; mixed operands are
    common at kernel boundaries — e.g. rank-varying dynamic offsets
    (functions of ``lax.axis_index``) next to replicated zero biases, or
    replicated scalars next to sharded activations.  Broadcasting the
    union onto every operand is semantically a no-op (each shard already
    holds the value it would hold) and unblocks the kernel path without
    ``check_vma=False``.  Off shard_map / with tracking disabled this
    returns the inputs unchanged."""
    union = frozenset().union(*(jax.typeof(x).vma for x in arrays))
    if not union:
        return arrays
    out = []
    for x in arrays:
        missing = tuple(sorted(union - jax.typeof(x).vma))
        out.append(lax.pcast(x, missing, to="varying") if missing else x)
    return tuple(out)


def match_vma(cotangent, primal):
    """Give a custom-VJP ``cotangent`` the vma of the ``primal`` input
    it belongs to — jax rejects a backward rule whose outputs vary
    differently from the corresponding primal inputs.

    Axes the cotangent varies on and the primal does not are summed
    (``psum``): the primal was implicitly broadcast over them on the way
    in, and the transpose of a broadcast is a sum — exactly what
    autodiff inserts for a plain op, and what
    ``parallel.distributed.reduce_gradients`` reads as "already reduced"
    (empty vma), so a replicated parameter's gradient is summed once,
    here, and not again there.  Axes the primal varies on and the
    cotangent does not are ``pcast`` up (a no-op on the values).
    ``None`` cotangents pass through; outside ``shard_map`` (or with
    ``check_vma=False``, where every vma reads empty and autodiff
    inserts no psum either) this is the identity."""
    if cotangent is None:
        return None
    want = jax.typeof(primal).vma
    have = jax.typeof(cotangent).vma
    extra = tuple(sorted(have - want))
    if extra:
        cotangent = lax.psum(cotangent, extra)
    missing = tuple(sorted(want - have))
    if missing:
        cotangent = lax.pcast(cotangent, missing, to="varying")
    return cotangent


def sds_with_vma(shape, dtype, *like):
    """``ShapeDtypeStruct`` whose vma is the union of the operands' —
    required for ``pallas_call`` outputs inside ``shard_map`` with
    ``check_vma=True``; a plain struct outside."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
