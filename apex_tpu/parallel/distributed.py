"""Distributed data parallelism — the DDP contract over XLA collectives.

TPU-native re-design of reference ``apex/parallel/distributed.py:129-639``.

The reference DDP is a *scheduling layer* over NCCL: backward hooks, dtype
buckets built in backward-arrival order, flatten/allreduce/unflatten on side
CUDA streams.  Under SPMD compilation all of that machinery dissolves — XLA
schedules and overlaps collectives itself (SURVEY.md §5) — but the DDP
*contract* is preserved:

* params synced across replicas at wrap time          (``broadcast_params``)
* grads averaged across replicas by step time          (``reduce_gradients``)
* ``delay_allreduce`` / ``no_sync``-style accumulation (``no_sync``)
* ``gradient_average`` + ``gradient_predivide_factor`` (pre/post divide to
  protect reduced-precision dynamic range, reference ``:445-454``)
* ``allreduce_always_fp32``                            (reference ``:442-457``)
* sub-groups / round-robin communicators → ``axis_index_groups`` on the HLO
  all-reduce (reference process groups ``:604-624``)

Usage inside ``shard_map``/``pmap`` over a mesh axis::

    ddp = DistributedDataParallel(axis_name="data",
                                  allreduce_always_fp32=True)
    grads = ddp.reduce_gradients(grads)        # inside the mapped fn

or functionally: ``reduce_gradients(grads, axis_name="data", ...)``.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def _note_collective(op: str, axis_names, tree_bytes: int, n: int,
                     dtype=None) -> None:
    """Report one collective's per-invocation traffic to the active
    telemetry recorder (ISSUE 5).  Runs at TRACE time — the byte counts
    are static aval properties — so the compiled program is unchanged
    and the event appears once per compile, not once per step.

    ``participants`` (ISSUE 10): the product of the collective's axis
    sizes, read at trace time, rides the event so the fleet merge can
    model each host's wire share (``prof.fleet``'s wait-vs-wire split)
    without re-deriving the mesh from the stream."""
    from .. import telemetry as _telemetry
    rec = _telemetry.get_recorder()
    if rec is not None and n:
        participants = 1
        try:
            names = (axis_names if isinstance(axis_names, (tuple, list))
                     else (axis_names,))
            for a in names:
                participants *= lax.axis_size(a)
        except Exception:
            participants = None
        rec.note_collective(op, axis_names, tree_bytes, n,
                            dtype=str(dtype) if dtype is not None else None,
                            participants=participants)


def _is_float(x):
    dt = getattr(x, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jnp.floating)


def vma_tracking_live(axis_name) -> bool:
    """Whether varying-manual-axes tracking is live on this trace.

    Under ``shard_map(check_vma=False)`` every aval reports an empty vma
    set, which must NOT be read as "already reduced"/"replicated" — there
    the implicit-broadcast transpose does not insert a psum either, so
    grads arrive per-shard.  ``axis_index`` is axis-varying by
    construction, so it probes tracking.  Shared by the gradient
    reduction here, the overflow agreement in ``training._por_varying``,
    and the ring-flash dispatch.
    """
    try:
        return axis_name in jax.typeof(lax.axis_index(axis_name)).vma
    except Exception:
        return False


def group_psum(x, axis_name: str, axis_index_groups=None):
    """``psum`` over ``axis_name``, optionally restricted to rank sub-groups.

    Sub-grouped all-reduce is the HLO ``replica_groups`` feature (reference
    process groups, SURVEY.md §5).  Lowering strategy, most to least
    scalable:

    1. native ``psum(axis_index_groups=...)`` where the trace allows it
       (pmap; shard_map raises NotImplementedError as of this jax version);
    2. butterfly (recursive-doubling) all-reduce over ``ppermute`` when all
       groups share a power-of-two size — O(|tensor|) memory, log2(k)
       collectives riding ICI, and a rank-invariant reduction tree (bitwise
       identical results on every member, like a real grouped all-reduce);
    3. fallback for irregular groups: ``all_gather`` + a static membership
       mask contraction (O(world x |tensor|) — fine on test meshes, not for
       pods; numerically fp32-accumulated).
    """
    if axis_index_groups is None:
        return lax.psum(x, axis_name)
    groups = [list(g) for g in axis_index_groups]
    try:
        return lax.psum(x, axis_name, axis_index_groups=groups)
    except NotImplementedError:
        pass
    sizes = {len(g) for g in groups}
    if len(sizes) == 1:
        k = sizes.pop()
        if k > 0 and (k & (k - 1)) == 0:
            return _group_psum_butterfly(x, axis_name, groups, k)
    return _group_psum_gather_mask(x, axis_name, groups)


def _group_psum_butterfly(x, axis_name: str, groups, k: int):
    """Grouped all-reduce as log2(k) XOR-partner exchange-and-add rounds.

    Every member of a group applies the SAME pairwise summation tree, so all
    members finish with bitwise-identical sums (commutativity of IEEE
    addition), matching the determinism contract of an HLO grouped
    all-reduce."""
    step = 1
    while step < k:
        perm = [(g[m ^ step], g[m]) for g in groups for m in range(k)]
        x = x + lax.ppermute(x, axis_name, perm)
        step <<= 1
    return x


def _group_psum_gather_mask(x, axis_name: str, groups):
    world = lax.axis_size(axis_name)
    import numpy as _np
    from ..amp._amp_state import maybe_print
    # O(world x |tensor|) on the wire — fine for a handful of hosts,
    # not for pods.  Warn ONCE per trace so an irregular BN group on a
    # large mesh doesn't silently take this path (VERDICT r3 weak #5);
    # tracing happens once per jit compile, so this is not a per-step
    # print.
    maybe_print(
        f"apex_tpu.parallel: grouped psum over irregular groups "
        f"{[len(g) for g in groups]} lowers to the masked-gather fallback "
        f"(all_gather of the full tensor across {world} ranks) — "
        f"equal power-of-two group sizes use the butterfly lowering "
        f"instead; not recommended on pods")
    member = _np.zeros((world, world), _np.float32)
    for g in groups:
        for i in g:
            for j in g:
                member[i, j] = 1.0
    idx = lax.axis_index(axis_name)
    gathered = lax.all_gather(x, axis_name)              # [world, ...]
    w = jnp.take(jnp.asarray(member), idx, axis=0)       # [world]
    out = jnp.tensordot(w, gathered.astype(jnp.float32), axes=1)
    return out.astype(jnp.asarray(x).dtype)


def reduce_gradients(grads,
                     axis_name: str,
                     *,
                     gradient_average: bool = True,
                     gradient_predivide_factor: float = 1.0,
                     allreduce_always_fp32: bool = False,
                     axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
                     world_size: Optional[int] = None,
                     bucket_store=None):
    """All-reduce a gradient pytree across ``axis_name`` replicas.

    Equivalent of ``allreduce_bucket`` (reference ``distributed.py:425-475``):
    optional fp32 upcast, predivide by ``gradient_predivide_factor`` before
    the reduce and postdivide by ``world/predivide`` after, so reduced-
    precision sums stay in range.

    ``axis_name`` may be a tuple of mesh axes (e.g. ``("data", "sp")``) —
    the DP contract then spans their product, as when a model is replicated
    over a 2-D data × sequence-parallel mesh.  ``axis_index_groups``
    requires a single axis.

    ``bucket_store`` (a :class:`~apex_tpu.multi_tensor.BucketStore` built
    from the grad tree) is the apex-DDP flat-bucket path: grads are packed
    into per-dtype flat buffers and the reduction is ONE ``psum`` per
    bucket — with ``allreduce_always_fp32`` casting at the bucket level —
    instead of one collective per leaf.  An already-``Packed`` ``grads``
    stays packed in the output.
    """
    axis_names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if len(axis_names) > 1 and axis_index_groups:
        raise ValueError("axis_index_groups requires a single axis name")
    full_world = 1
    for a in axis_names:
        full_world *= lax.axis_size(a)
    explicit_world = world_size is not None
    if world_size is None:
        world_size = full_world
        if axis_index_groups:
            world_size = len(axis_index_groups[0])

    _vma_tracking = vma_tracking_live(axis_names[0])

    def _axes_still_varying(g):
        """Mesh axes this grad still varies over (needs explicit psum).
        Axes absent from the vma set were already *summed*: shard_map
        autodiff inserts the psum itself when differentiating w.r.t.
        replicated params (the transpose of the implicit broadcast), and
        the custom-VJP ops do the same (``pallas_compat.match_vma``)."""
        if not _vma_tracking:
            return axis_names
        vma = jax.typeof(g).vma
        return tuple(a for a in axis_names if a in vma)

    # Telemetry collector: per-leaf (or per-bucket) psum bytes summed at
    # trace time into ONE ``collective`` event per reduce_gradients call.
    coll = {"bytes": 0, "n": 0, "dtypes": set()}

    def one(g):
        if not _is_float(g):
            return g
        need = _axes_still_varying(g)
        if need:
            wire_dtype = (jnp.dtype(jnp.float32) if allreduce_always_fp32
                          else jnp.dtype(g.dtype))
            coll["bytes"] += ((math.prod(g.shape) if g.shape else 1)
                              * wire_dtype.itemsize)
            coll["n"] += 1
            coll["dtypes"].add(str(wire_dtype))
        if not need:
            # Fully pre-summed by the implicit psum — which spans the FULL
            # axes (subgroup structure is invisible to the transpose), so
            # average over the full product regardless of axis_index_groups —
            # unless the caller passed world_size, which always wins (same
            # contract as the explicit branch below).  With
            # gradient_average=False the explicit branch's predivide/
            # postmultiply cancel to a plain sum, which is what the implicit
            # psum already produced, so the raw sum is returned either way.
            if gradient_average:
                denom = world_size if explicit_world else full_world
                return (g / denom).astype(jnp.asarray(g).dtype)
            return g
        orig_dtype = jnp.asarray(g).dtype
        if allreduce_always_fp32:
            g = jnp.asarray(g, jnp.float32)
        if gradient_predivide_factor != 1.0:
            g = g / gradient_predivide_factor
        g = group_psum(g, need if len(need) > 1 else need[0],
                       axis_index_groups)
        if gradient_average:
            # After implicit (axes not in `need`) + explicit sums the grad
            # is summed over the full product; with subgroups (single axis,
            # nothing implicit) it is summed over the group only.  An
            # explicitly passed world_size always wins (public contract).
            denom = (world_size if (axis_index_groups or explicit_world)
                     else full_world)
            postdiv = denom / gradient_predivide_factor
            if postdiv != 1.0:
                g = g / postdiv
        elif gradient_predivide_factor != 1.0:
            g = g * gradient_predivide_factor
        if allreduce_always_fp32:
            g = g.astype(orig_dtype)
        return g

    from ..multi_tensor.buckets import Packed

    def _wire_dtype():
        # One dtype crossed the wire, or an honest "mixed" label — a
        # last-leaf-wins dtype would misattribute the summed bytes.
        if len(coll["dtypes"]) == 1:
            return next(iter(coll["dtypes"]))
        return "mixed" if coll["dtypes"] else None

    if bucket_store is not None or isinstance(grads, Packed):
        packed = (grads if isinstance(grads, Packed)
                  else bucket_store.pack(grads))
        # Collective/compute overlap (ISSUE 7): issue the per-bucket
        # psums in REVERSE-TOPOLOGICAL bucket order — each bucket's
        # collective is emitted as soon as its grads are final (its
        # pack depends only on its own leaves, so with a chunked store
        # — BucketStore(max_bucket_elems=...) — the deepest layers'
        # psum starts while earlier layers are still differentiating;
        # XLA's latency-hiding scheduler turns the issue order + closed
        # data deps into async start/done pairs riding the wire under
        # the remaining backward).  One monolithic bucket degenerates
        # to the old end-of-backward barrier.
        order = (bucket_store.reverse_topological_order()
                 if bucket_store is not None
                 else tuple(range(len(packed.data))))
        data = list(packed.data)
        for bi in order:
            data[bi] = one(data[bi])
        out = Packed(data=tuple(data), rest=packed.rest)
        _note_collective("psum", axis_names, coll["bytes"], coll["n"],
                         dtype=_wire_dtype())
        if isinstance(grads, Packed):
            return out
        return bucket_store.unpack(out)
    out = jax.tree_util.tree_map(one, grads)
    _note_collective("psum", axis_names, coll["bytes"], coll["n"],
                     dtype=_wire_dtype())
    return out


def broadcast_params(params, axis_name: str,
                     root: int = 0,
                     axis_index_groups=None):
    """Make every replica's params equal to ``root``'s (reference ctor
    broadcast, ``distributed.py:253``).  Implemented as mask+psum — the XLA
    idiom for broadcast-from-rank."""
    idx = lax.axis_index(axis_name)
    mask = (idx == root).astype(jnp.float32)

    def one(p):
        if not _is_float(p):
            return p
        contrib = jnp.asarray(p, jnp.float32) * mask
        return group_psum(contrib, axis_name, axis_index_groups).astype(
            jnp.asarray(p).dtype)

    return jax.tree_util.tree_map(one, params)


class DistributedDataParallel:
    """Object form carrying the DDP options (reference ctor flags).

    ``message_size``, ``num_allreduce_streams`` and ``delay_allreduce`` are
    accepted for API parity; on TPU message bucketing and stream scheduling
    are XLA's responsibility, so they only affect bookkeeping (``delay_
    allreduce`` is honored: reduction happens in ``reduce_gradients`` which
    the caller invokes at the end of backward either way — there are no
    per-param hooks to delay).
    """

    def __init__(self,
                 module: Optional[Callable] = None,
                 axis_name: str = "data",
                 message_size: int = 10000000,
                 delay_allreduce: bool = False,
                 shared_param=None,
                 allreduce_trigger_params=None,
                 retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False,
                 num_allreduce_streams: int = 1,
                 allreduce_communicators=None,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 axis_index_groups=None,
                 prof: bool = False,
                 bucket_store=None):
        if shared_param is not None:
            raise ValueError("shared_param is deprecated (reference parity: "
                             "distributed.py:149-151); use delay_allreduce.")
        self.module = module
        self.axis_name = axis_name
        self.delay_allreduce = delay_allreduce
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.axis_index_groups = axis_index_groups
        self.retain_allreduce_buffers = retain_allreduce_buffers
        self.prof = prof
        self.bucket_store = bucket_store
        self._disable_allreduce = False

    # Forward passes through to the wrapped module (reference module wrapper).
    def __call__(self, *args, **kwargs):
        if self.module is None:
            raise ValueError("DistributedDataParallel wraps no module")
        return self.module(*args, **kwargs)

    def sync_params(self, params, root: int = 0):
        return broadcast_params(params, self.axis_name, root,
                                self.axis_index_groups)

    def reduce_gradients(self, grads):
        if self._disable_allreduce:
            return grads
        scope = jax.named_scope("apex_tpu.ddp.allreduce")  # prof marker
        with scope:
            return reduce_gradients(
                grads, self.axis_name,
                gradient_average=self.gradient_average,
                gradient_predivide_factor=self.gradient_predivide_factor,
                allreduce_always_fp32=self.allreduce_always_fp32,
                axis_index_groups=self.axis_index_groups,
                bucket_store=self.bucket_store)

    @contextlib.contextmanager
    def no_sync(self):
        """Disable grad reduction inside the context (reference
        ``disable_allreduce`` flag, ``distributed.py:275-279``) — the grad
        accumulation idiom.  Trace-time switch, like the reference's Python
        flag."""
        saved = self._disable_allreduce
        self._disable_allreduce = True
        try:
            yield
        finally:
            self._disable_allreduce = saved

    def wrap_grad_fn(self, grad_fn: Callable) -> Callable:
        """Return a grad_fn whose output grads are reduced — the "hook"
        equivalent for functional code."""
        def wrapped(*args, **kwargs):
            out = grad_fn(*args, **kwargs)
            if isinstance(out, tuple) and len(out) == 2:
                value, grads = out
                return value, self.reduce_gradients(grads)
            return self.reduce_gradients(out)
        return wrapped


class Reducer:
    """Manually-triggered allreduce of a param/grad tree (reference
    ``Reducer``, ``distributed.py:89-126``)."""

    def __init__(self, module_or_grads_list=None, axis_name: str = "data",
                 axis_index_groups=None):
        self.axis_name = axis_name
        self.axis_index_groups = axis_index_groups
        self.target = module_or_grads_list

    def reduce(self, tree=None, average: bool = True):
        tree = tree if tree is not None else self.target
        return reduce_gradients(tree, self.axis_name,
                                gradient_average=average,
                                axis_index_groups=self.axis_index_groups)
