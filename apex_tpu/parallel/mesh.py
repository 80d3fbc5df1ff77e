"""Unified N-D sharding frontend: declare a DP×FSDP×TP mesh once,
derive every placement from it (ISSUE 12 tentpole).

The parallel/ pillar grew as independent wrappers — DDP psum, zero1,
pipeline, tensor_parallel, expert_parallel, ring_attention — each
hard-coding its own axis name and device layout.  :class:`MeshPlan` is
the single declaration they compose on (the NamedSharding/PartitionSpec
helper idiom of SNIPPETS.md [2], pjit-style references [1]/[3]):

* **axis names, sizes, device order** are stated ONCE
  (``MeshPlan(dp=2, fsdp=4)``), per-process under multi-host
  (``jax.devices()`` spans every process after
  :func:`apex_tpu.parallel.multiproc.initialize`);
* **parameter / optimizer-state / data placements** are derived —
  ``plan.batch_sharding()``, ``plan.state_spec(state)``,
  ``plan.named(...)`` — never re-declared per call site;
* **ZeRO-style state partitioning** layers over the flat-bucket store
  (:class:`~apex_tpu.multi_tensor.BucketStore`):

  ========  =======================================================
  level     what is sharded over the ``fsdp`` axis
  ========  =======================================================
  1 / 2     optimizer state; gradients are reduce-scattered (the
            ZeRO-2 wire schedule — ``zero1`` already moves grads as
            per-chunk scatters, so stages 1 and 2 coincide in SPMD)
  3         params AND optimizer state: params live as sharded flat
            buckets; the full tree exists only INSIDE the step
  ========  =======================================================

The ZeRO-3 trick is autodiff-native: the stored params are sharded flat
buckets, and a ``param_view`` (:func:`apex_tpu.training.make_train_step`)
all-gathers + unpacks them INSIDE the differentiated loss.  The
transpose of that gather **is** the reduce-scatter (``reduce_scatter``
HLO — the same primitive ``lax.psum_scatter`` lowers to), so the
backward emits exactly ZeRO's grad schedule with no hand-written VJP;
with a chunked store (``max_bucket_elems``) the per-bucket gathers and
scatters close their data dependencies bucket-by-bucket and XLA's
latency-hiding scheduler overlaps them with the surrounding compute —
the same reverse-topological machinery
:func:`apex_tpu.parallel.reduce_gradients` uses for chunked psums.

Wired end to end with the pre-built hard parts:

* **elastic reshard** — ZeRO-3 params and moments are exactly the flat
  padded buckets ``apex_tpu.checkpoint`` reshards N→M on read; save
  with ``bucket_layout=plan.bucket_layout(store)`` and restore onto a
  different mesh (``tests/test_checkpoint.py``);
* **AOT warmup** — :meth:`MeshTrainStep.init` device_puts every leaf
  with a COMMITTED NamedSharding, so ``cache.abstractify`` pins the
  placements and :meth:`StepPipeline.warmup
  <apex_tpu.runtime.StepPipeline.warmup>` compiles the sharded step
  before step 0 (zero steady-state retraces);
* **fleet attribution** — every collective is noted per mesh AXIS
  (dp/fsdp/tp), so ``prof.fleet``'s wait-vs-wire split and the
  timeline byte totals attribute traffic per axis.

Usage::

    from apex_tpu.parallel import mesh

    plan = mesh.MeshPlan(dp=2, fsdp=4)            # 8 devices, 2-D
    ms = mesh.make_mesh_train_step(loss_fn, training.adam(1e-3), plan,
                                   zero=3, opt_level="O2")
    state = ms.init(params)                       # sharded + committed
    step = ms.jit_step(state)                     # shard_map + jit
    state, metrics = step(state, plan.device_put_batch(batch))

    # or through the pipelined runtime:
    pipe = runtime.StepPipeline(ms.step_fn, k=8,
                                wrap=ms.pipeline_wrap(state))
    pipe.warmup(state, window)                    # AOT, sharded
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..multi_tensor.buckets import (BucketStore, Packed, cached_store,
                                    padded_shard_len)
from .distributed import _note_collective
from .zero import Zero1State, _shard_one

__all__ = ["MeshPlan", "MeshTrainStep", "make_mesh_train_step",
           "zero_sharded", "MeshZeroState"]


class MeshPlan:
    """One declaration of a DP×FSDP×TP device mesh.

    ``dp`` replicas see different data and hold full state; ``fsdp``
    replicas see different data and SHARD state (the ZeRO axis); ``tp``
    replicas see the same data and shard tensors inside the model (use
    ``plan.tp_axis`` with :mod:`apex_tpu.parallel.tensor_parallel`).
    Sizes must multiply to ``len(devices)``.

    ``devices`` defaults to ``jax.devices()`` — under multi-host
    (:func:`apex_tpu.parallel.multiproc.initialize`) that is the GLOBAL
    device list in a process-consistent order, so every process
    constructs the same mesh and owns its local slice of it.
    """

    def __init__(self, *, dp: int = 1, fsdp: int = 1, tp: int = 1,
                 devices: Optional[Sequence] = None,
                 axis_names: Tuple[str, str, str] = ("dp", "fsdp", "tp")):
        if len(tuple(axis_names)) != 3:
            raise ValueError(f"axis_names must name (dp, fsdp, tp), got "
                             f"{axis_names!r}")
        if min(dp, fsdp, tp) < 1:
            raise ValueError(
                f"axis sizes must be >= 1, got dp={dp} fsdp={fsdp} tp={tp}")
        if devices is None:
            devices = jax.devices()
        devices = np.asarray(devices, dtype=object)
        if devices.size != dp * fsdp * tp:
            raise ValueError(
                f"MeshPlan needs dp*fsdp*tp == len(devices): "
                f"{dp}*{fsdp}*{tp} != {devices.size} — size the plan to "
                f"the device count (jax.device_count()={jax.device_count()})")
        self.axis_names = tuple(axis_names)
        self.dp, self.fsdp, self.tp = int(dp), int(fsdp), int(tp)
        self.mesh = Mesh(devices.reshape(self.dp, self.fsdp, self.tp),
                         self.axis_names)

    @classmethod
    def auto(cls, *, fsdp: Optional[int] = None, tp: int = 1,
             devices: Optional[Sequence] = None, **kw) -> "MeshPlan":
        """Fill ``dp`` from the device count: ``fsdp`` defaults to all
        devices not claimed by ``tp`` (pure FSDP, the memory-optimal
        default), ``dp`` to the remainder."""
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        if fsdp is None:
            if n % tp:
                raise ValueError(f"{n} devices not divisible by tp={tp}")
            fsdp = n // tp
        if n % (fsdp * tp):
            raise ValueError(
                f"{n} devices not divisible by fsdp*tp={fsdp * tp}")
        return cls(dp=n // (fsdp * tp), fsdp=fsdp, tp=tp,
                   devices=devices, **kw)

    # -- axis names ----------------------------------------------------------
    @property
    def dp_axis(self) -> str:
        return self.axis_names[0]

    @property
    def fsdp_axis(self) -> str:
        return self.axis_names[1]

    @property
    def tp_axis(self) -> str:
        return self.axis_names[2]

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes whose replicas consume DIFFERENT data (batch sharding +
        gradient reduction span their product)."""
        return (self.dp_axis, self.fsdp_axis)

    @property
    def all_axes(self) -> Tuple[str, ...]:
        """Every mesh axis — the step's ``axis_name`` (overflow
        agreement and metric pmean span the full mesh)."""
        return self.axis_names

    @property
    def world_size(self) -> int:
        return self.dp * self.fsdp * self.tp

    @property
    def data_world(self) -> int:
        """Number of distinct data shards (the gradient-mean divisor)."""
        return self.dp * self.fsdp

    def __repr__(self):
        return (f"MeshPlan({self.dp_axis}={self.dp} x "
                f"{self.fsdp_axis}={self.fsdp} x {self.tp_axis}={self.tp} "
                f"over {self.world_size} device(s))")

    # -- derived placements --------------------------------------------------
    def named(self, *spec) -> NamedSharding:
        """``NamedSharding(mesh, P(*spec))`` — the one constructor every
        placement below derives from."""
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return self.named()

    @property
    def batch_spec(self) -> P:
        """Per-step batch: leading (batch) dim sharded over dp×fsdp,
        replicated over tp."""
        return P(self.data_axes)

    def batch_sharding(self) -> NamedSharding:
        return self.named(self.data_axes)

    def window_sharding(self) -> NamedSharding:
        """A ``[K, batch, ...]`` staged window: leading K axis unsharded
        (the device-loop axis), batch axis over dp×fsdp — pass as
        ``stage_windows(..., device=plan.window_sharding())``."""
        return self.named(None, self.data_axes)

    @property
    def flat_spec(self) -> P:
        """A ZeRO flat bucket (1-D, padded to divide): sharded over the
        fsdp axis."""
        return P(self.fsdp_axis)

    def flat_sharding(self) -> NamedSharding:
        return self.named(self.fsdp_axis)

    def device_put_batch(self, batch):
        """Place one host batch onto the mesh (committed — the AOT
        warmup pins this placement).  Multi-host callers feed their
        per-process shard; single-process callers the global batch."""
        sh = self.batch_sharding()
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            return multihost_utils.host_local_array_to_global_array(
                batch, self.mesh, self.batch_spec)
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), batch)

    def device_put_window(self, window):
        """Stage a ``[K, batch, ...]`` stacked window (the
        :func:`apex_tpu.runtime.window_batches` shape): leading K axis
        unsharded, batch axis over dp×fsdp.  Multi-host callers feed
        their per-process window; single-process the global one."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            return multihost_utils.host_local_array_to_global_array(
                window, self.mesh, P(None, *self.batch_spec))
        sh = self.window_sharding()
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), window)

    def shard_map(self, fn, in_specs, out_specs):
        """``shard_map`` over this plan's mesh."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs)

    # -- ledger --------------------------------------------------------------
    def state_bytes(self, tree) -> dict:
        """Placement ledger of a (state) pytree: global bytes vs the
        bytes ONE device actually holds under the committed shardings —
        the ZeRO memory claim as an auditable number
        (``bench.py`` gates ZeRO-3 at ~1/shard_count).  Leaves without
        a sharding count as replicated."""
        glob = per_dev = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if not hasattr(leaf, "dtype") or not hasattr(leaf, "shape"):
                continue
            itemsize = jnp.dtype(leaf.dtype).itemsize
            nbytes = itemsize * int(math.prod(leaf.shape) if leaf.shape else 1)  # jaxlint: disable=J008 -- static shape arithmetic (aval metadata), no device round-trip
            glob += nbytes
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and leaf.shape:
                try:
                    shard_shape = sharding.shard_shape(tuple(leaf.shape))
                    per_dev += itemsize * int(math.prod(shard_shape))  # jaxlint: disable=J008 -- static shape/sharding arithmetic, no device round-trip
                    continue
                except Exception:
                    pass
            per_dev += nbytes
        return {"global_bytes": glob, "bytes_per_device": per_dev,
                "ratio": round(per_dev / glob, 4) if glob else None}

    def bucket_layout(self, store: BucketStore) -> dict:
        """Checkpoint-manifest bucket descriptor for THIS plan's shard
        count (:func:`apex_tpu.checkpoint.bucket_layout`) — what
        elastic N→M reshard-on-read re-slices against."""
        return store.shard_layout(self.fsdp)


# -- ZeRO over the plan -------------------------------------------------------

class MeshZeroState(NamedTuple):
    """Optimizer state of :func:`zero_sharded`: one inner state per
    flat bucket, each sharded over the plan's fsdp axis."""
    inner: Tuple[Any, ...]


def _pad_bucket(b, num_shards: int):
    return jnp.pad(b, (0, padded_shard_len(b.size, num_shards) - b.size))


def _require_elementwise(tx) -> None:
    if not getattr(tx, "elementwise", False):
        raise ValueError(
            "zero_sharded requires an optimizer that declares "
            "elementwise=True (adam/sgd qualify) — per-tensor-norm "
            "optimizers compute wrong trust ratios on flat chunks; see "
            "parallel.zero.zero1 for the full contract")


def zero_sharded(tx, plan: MeshPlan, *, level: int = 2,
                 decay_flags=None, **store_kw):
    """ZeRO state partitioning over ``plan``'s fsdp axis, flat-bucket
    substrate.  Returns a :class:`~apex_tpu.training.FunctionalOptimizer`.

    * ``level`` 1/2 — params replicated (plain pytree), optimizer state
      sharded; gradients reduce-scattered over fsdp and psummed over dp
      (stages 1 and 2 coincide in SPMD: grads already move as per-chunk
      scatters, never materializing a full per-rank copy past backward).
    * ``level`` 3 — params themselves stored as fsdp-sharded flat
      buckets (:class:`~apex_tpu.multi_tensor.Packed`); build the step
      through :func:`make_mesh_train_step`, which installs the
      gather-in-loss ``param_view`` whose transpose IS the grad
      reduce-scatter.

    Both run inside ``shard_map`` with ``reduce_grads=False`` (the
    optimizer owns every reduction) and ``axis_name=plan.all_axes``
    (the step still needs the mesh-wide overflow agreement).
    ``store_kw`` (``max_bucket_elems``, ``decay_mask``) configure the
    underlying :class:`~apex_tpu.multi_tensor.BucketStore` for levels
    1/2 (which pack the tree themselves); level 3 consumes pre-packed
    buckets, so the caller passes their store's ``decay_flags``
    instead."""
    from ..training import FunctionalOptimizer

    _require_elementwise(tx)
    if level not in (1, 2, 3):
        raise ValueError(f"zero level must be 1, 2, or 3, got {level}")
    if level < 3:
        return _zero12_tx(tx, plan, FunctionalOptimizer, store_kw)
    return _zero3_tx(tx, plan, FunctionalOptimizer, decay_flags=decay_flags)


def _zero12_tx(tx, plan: MeshPlan, FunctionalOptimizer, store_kw):
    """Replicated params, sharded state — the zero1 bucketed machinery
    generalized to the 2-D data mesh (dp psum on the scattered chunk,
    mean over the full data world)."""
    cell = {}

    def _store(params) -> BucketStore:
        return cached_store(cell, params, **store_kw)

    def init(params):
        packed = _store(params).pack(params)
        inner = tuple(tx.init(_pad_bucket(b, plan.fsdp))
                      for b in packed.data)
        return Zero1State(inner=inner)

    def update(grads, state, params, *, apply_mask=None, **kw):
        store = _store(params)
        idx = lax.axis_index(plan.fsdp_axis)
        packed_p = store.pack(params)
        packed_g = store.pack(grads, cast=True)
        new_data = list(packed_p.data)
        new_inner = list(state.inner)
        # Reverse-topological issue order: the deepest layers' scatter
        # starts while earlier layers still differentiate (ISSUE 7
        # machinery, reused for the mesh schedule).
        for bi in store.reverse_topological_order():
            bkw = (kw if store.decay_flags[bi]
                   else {**kw, "weight_decay": 0.0})
            flat_new, ni = _shard_one(
                packed_p.data[bi],
                packed_g.data[bi].astype(packed_p.data[bi].dtype),
                state.inner[bi], tx, plan.fsdp, idx, plan.fsdp,
                plan.fsdp_axis, apply_mask, bkw,
                pre_axes=(plan.dp_axis,), denom=plan.data_world)
            new_data[bi] = flat_new
            new_inner[bi] = ni
        out = Packed(data=tuple(new_data), rest=packed_p.rest)
        return store.unpack(out), Zero1State(inner=tuple(new_inner))

    return FunctionalOptimizer(init=init, update=update)


def _zero3_tx(tx, plan: MeshPlan, FunctionalOptimizer, decay_flags=None):
    """Sharded params AND state: ``init`` takes the PACKED padded
    params; ``update`` receives per-chunk gradients already summed over
    fsdp (the ``param_view`` gather's transpose) and finishes the mean
    with the dp psum.  ``decay_flags`` are the packing store's
    per-bucket weight-decay flags (the no-decay buckets a ``decay_mask``
    split off get ``weight_decay=0.0``, same contract as the bucketed
    optimizers)."""

    def init(packed: Packed):
        if not isinstance(packed, Packed):
            raise TypeError(
                "zero level 3 stores params as fsdp-sharded flat buckets "
                "— build the step with make_mesh_train_step(..., zero=3), "
                "whose init packs the tree for you")
        return MeshZeroState(inner=tuple(tx.init(b) for b in packed.data))

    def update(grads: Packed, state, params: Packed, *,
               apply_mask=None, **kw):
        new_data = list(params.data)
        new_inner = list(state.inner)
        for bi in range(len(params.data)):
            g = grads.data[bi].astype(params.data[bi].dtype)
            if plan.dp > 1:
                _note_collective(
                    "psum", plan.dp_axis,
                    g.size * jnp.dtype(g.dtype).itemsize, 1, dtype=g.dtype)
                g = lax.psum(g, plan.dp_axis)
            g = g / plan.data_world
            bkw = (kw if decay_flags is None or decay_flags[bi]
                   else {**kw, "weight_decay": 0.0})
            new_p, ni = tx.update(g, state.inner[bi], params.data[bi],
                                  apply_mask=apply_mask, **bkw)
            new_data[bi] = new_p
            new_inner[bi] = ni
        return (Packed(data=tuple(new_data), rest=params.rest),
                MeshZeroState(inner=tuple(new_inner)))

    return FunctionalOptimizer(init=init, update=update,
                               elementwise=True)


# -- the step frontend --------------------------------------------------------

def _gather_view(store: BucketStore, plan: MeshPlan,
                 gather_dtype=None) -> Callable:
    """The ZeRO-3 ``param_view``: per-bucket all-gather over fsdp +
    unpack back to the template tree.  Runs INSIDE the differentiated
    loss, so its transpose (slice-pad + ``reduce_scatter``) is the grad
    schedule.  Per-invocation bytes are noted per bucket on the fsdp
    axis — once for the forward gather, once for the backward scatter
    the transpose will emit.

    ``gather_dtype`` (the ROADMAP mesh-round-2 bf16-gather): cast each
    fsdp-sharded flat bucket to the wire dtype BEFORE the gather and
    back after, halving wire bytes both ways — the transpose of the
    downcast is the upcast, so the backward reduce-scatters bf16 grad
    chunks and hands the optimizer fp32 again.  The fp32 MASTERS are
    untouched (only the in-step view quantizes); ``None`` keeps the
    bitwise fp32 path.  Only float buckets wider than the wire dtype
    cast — an already-narrow bucket ships as-is."""
    wire = None if gather_dtype is None else jnp.dtype(gather_dtype)

    def view(packed: Packed):
        full = []
        for bi, b in enumerate(store.buckets):
            buf = packed.data[bi]
            cast = (wire is not None
                    and jnp.issubdtype(buf.dtype, jnp.floating)
                    and jnp.dtype(buf.dtype).itemsize > wire.itemsize)
            sent = buf.astype(wire) if cast else buf
            nbytes = (sent.size * plan.fsdp
                      * jnp.dtype(sent.dtype).itemsize)
            _note_collective("all_gather", plan.fsdp_axis, nbytes, 1,
                             dtype=sent.dtype)
            _note_collective("reduce_scatter", plan.fsdp_axis, nbytes, 1,
                             dtype=sent.dtype)
            g = lax.all_gather(sent, plan.fsdp_axis, tiled=True)
            g = g.astype(buf.dtype) if cast else g
            full.append(g[:b.size])
        return store.unpack(Packed(data=tuple(full), rest=packed.rest))
    return view


class MeshTrainStep(NamedTuple):
    """Everything :func:`make_mesh_train_step` derived from one plan.

    ``step_fn`` is the per-step function for ``shard_map`` (feed it to
    :class:`~apex_tpu.runtime.StepPipeline` with ``wrap=
    ms.pipeline_wrap()``); ``init`` places every leaf with a COMMITTED
    NamedSharding so AOT warmup pins the layout."""
    plan: MeshPlan
    zero: int
    init: Callable               # (params, model_state=None) -> TrainState
    step_fn: Callable            # (state, batch) -> (state, metrics)
    state_spec: Callable         # (state) -> TrainState of PartitionSpecs
    gather_params: Callable      # (state) -> full replicated param tree
    store: Optional[BucketStore]  # zero-3 bucket index map (else None)

    def wrap(self, fn, state):
        """``shard_map`` wrap of a loop function ``(state, window,
        valid) -> (state, metrics)`` (the StepPipeline contract): state
        by its derived spec, window batch-sharded with the leading K
        axis unsharded, valid mask and metrics replicated."""
        plan = self.plan
        spec = self.state_spec(state)
        return plan.shard_map(
            fn, in_specs=(spec, _tree_of(P(None, *plan.batch_spec)), P()),
            out_specs=(spec, P()))

    def pipeline_wrap(self, state):
        """The ``wrap=`` argument for :class:`StepPipeline`."""
        return lambda fn: self.wrap(fn, state)

    def jit_step(self, state, *, donate: bool = True):
        """One jitted sharded step ``(state, batch) -> (state,
        metrics)`` — the non-pipelined path."""
        plan = self.plan
        spec = self.state_spec(state)

        def stepped(s, b):
            return self.step_fn(s, b)

        mapped = plan.shard_map(stepped,
                                in_specs=(spec, _tree_of(plan.batch_spec)),
                                out_specs=(spec, P()))
        return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def _tree_of(spec):
    # shard_map treats a bare PartitionSpec as a prefix for the whole
    # subtree — the batch pytree needs no per-leaf enumeration.
    return spec


def make_mesh_train_step(loss_fn: Callable, tx, plan: MeshPlan, *,
                         zero: int = 2,
                         opt_level: str = "O2",
                         max_bucket_elems: Optional[int] = None,
                         decay_mask=None,
                         gather_dtype=None,
                         has_model_state: bool = False,
                         **train_kw) -> MeshTrainStep:
    """Build a sharded training step from one :class:`MeshPlan`.

    ``loss_fn`` takes the FULL parameter tree (as always);
    ``tx`` is an elementwise :class:`~apex_tpu.training.
    FunctionalOptimizer` (``training.adam``/``training.sgd``); ``zero``
    picks the state-partitioning level (table in the module docstring).
    Extra ``train_kw`` pass through to
    :func:`~apex_tpu.training.make_train_step` (loss_scale,
    accum_steps, scale_window, ...).

    ZeRO-3 restriction: ``opt_level`` must keep fp32 storage (O0/O1/O2/O4
    — master weights are the flat buckets); O3's bf16 storage would
    need per-bucket keep-norm splits and is rejected loudly.

    ``gather_dtype`` (ZeRO-3 only): wire dtype for the ``param_view``
    all-gather / grad reduce-scatter — ``jnp.bfloat16`` halves the
    per-step FSDP wire bytes while the stored fp32 masters stay exact
    (the compute cast was shipping bf16 into the matmuls anyway; the
    bf16 wire moves the rounding one op earlier).  ``None`` (default)
    keeps the bitwise fp32 wire.
    """
    from .. import training

    if zero not in (1, 2, 3):
        raise ValueError(f"zero level must be 1, 2, or 3, got {zero}")
    if zero == 3 and opt_level not in ("O0", "O1", "O2", "O4"):
        raise ValueError(
            f"zero=3 stores params as fp32 flat buckets (the masters); "
            f"opt_level {opt_level!r} stores reduced precision — use "
            f"O0/O1/O2/O4, or zero<=2 for O3")

    store_kw = {}
    if max_bucket_elems is not None:
        store_kw["max_bucket_elems"] = max_bucket_elems
    if decay_mask is not None:
        store_kw["decay_mask"] = decay_mask

    if zero != 3 and gather_dtype is not None:
        raise ValueError(
            "gather_dtype shapes the ZeRO-3 param_view wire; zero<3 "
            "replicates params and never gathers them — drop the "
            "argument or use zero=3")

    if zero < 3:
        z_tx = zero_sharded(tx, plan, level=zero, **store_kw)
        init_fn, step_fn = training.make_train_step(
            loss_fn, z_tx, opt_level=opt_level,
            axis_name=plan.all_axes, reduce_grads=False,
            has_model_state=has_model_state, **train_kw)

        def init(params, model_state=None):
            return _place_state(init_fn(params, model_state), plan, zero)

        def state_spec(state):
            return _derive_spec(state, plan, zero)

        def gather_params(state):
            return state.params

        return MeshTrainStep(plan=plan, zero=zero, init=init,
                             step_fn=step_fn, state_spec=state_spec,
                             gather_params=gather_params, store=None)

    # -- zero 3 --------------------------------------------------------------
    _require_elementwise(tx)
    cell: dict = {}              # cached_store signature -> BucketStore
    z3_holder: dict = {}         # id(store) -> (init_fn, step_fn)

    def _build(params_template):
        store = cached_store(cell, params_template, **store_kw)
        built = z3_holder.get(id(store))
        if built is None:
            z_tx = zero_sharded(tx, plan, level=3,
                                decay_flags=store.decay_flags)
            built = training.make_train_step(
                loss_fn, z_tx, opt_level=opt_level,
                axis_name=plan.all_axes, reduce_grads=False,
                has_model_state=has_model_state,
                param_view=_gather_view(store, plan, gather_dtype),
                **train_kw)
            z3_holder.clear()            # one live template at a time
            z3_holder[id(store)] = built
            z3_holder["latest"] = built
        return store, built

    def init(params, model_state=None):
        store, (init_fn, _) = _build(params)
        packed = store.pack(params)
        packed = Packed(
            data=tuple(_pad_bucket(b, plan.fsdp) for b in packed.data),
            rest=packed.rest)
        return _place_state(init_fn(packed, model_state), plan, 3)

    def step_fn(state, batch):
        built = z3_holder.get("latest")
        if built is None:
            raise RuntimeError(
                "make_mesh_train_step(zero=3): call ms.init(params) "
                "before using step_fn — the bucket index map is built "
                "from the first init's parameter template")
        return built[1](state, batch)

    def state_spec(state):
        return _derive_spec(state, plan, 3)

    def gather_params(state):
        # Full replicated param tree from the sharded buckets — the
        # eval/export interchange boundary, on demand, NEVER in the
        # hot step.
        store = _latest_store(cell)
        full = []
        for bi, b in enumerate(store.buckets):
            arr = jax.device_get(state.params.data[bi])  # jaxlint: disable=J001 -- explicit interchange boundary: exporting sharded params to a host tree
            full.append(jnp.asarray(np.asarray(arr)[:b.size]))
        return store.unpack(Packed(data=tuple(full),
                                   rest=state.params.rest))

    return MeshTrainStep(plan=plan, zero=3, init=init, step_fn=step_fn,
                         state_spec=state_spec,
                         gather_params=gather_params,
                         store=_StoreRef(cell))


def _latest_store(cell: dict) -> BucketStore:
    if not cell:
        raise RuntimeError(
            "ZeRO-3 bucket store not built yet — call ms.init(params) "
            "first")
    return next(reversed(cell.values()))


class _StoreRef:
    """Late-bound handle to the ZeRO-3 BucketStore (built at ``init``):
    ``ms.store()`` returns it, attribute access passes through."""

    def __init__(self, cell):
        self._cell = cell

    def __call__(self) -> BucketStore:
        return _latest_store(self._cell)

    def __getattr__(self, name):
        return getattr(_latest_store(self._cell), name)


def _leaf_spec_flat(plan: MeshPlan):
    def spec(leaf):
        return plan.flat_spec if jnp.ndim(leaf) >= 1 else P()
    return spec


def _derive_spec(state, plan: MeshPlan, zero: int):
    """TrainState of PartitionSpecs for the sharded step: flat (1-D)
    optimizer/param buckets over fsdp, everything else replicated."""
    from ..training import TrainState

    spec_flat = _leaf_spec_flat(plan)
    if zero >= 3:
        params_spec = Packed(
            data=tuple(plan.flat_spec for _ in state.params.data),
            rest=tuple(P() for _ in state.params.rest))
    else:
        params_spec = jax.tree_util.tree_map(lambda _: P(), state.params)
    opt_spec = jax.tree_util.tree_map(spec_flat, state.opt_state)
    ms_spec = jax.tree_util.tree_map(lambda _: P(), state.model_state) \
        if state.model_state is not None else P()
    scaler_spec = jax.tree_util.tree_map(lambda _: P(), state.scaler)
    return TrainState(params=params_spec, opt_state=opt_spec,
                      scaler=scaler_spec, model_state=ms_spec)


def _place_state(state, plan: MeshPlan, zero: int):
    """device_put every leaf onto its derived NamedSharding — COMMITTED
    placements, so ``cache.abstractify`` pins them for AOT warmup and
    checkpoint restore re-places leaves correctly."""
    spec = _derive_spec(state, plan, zero)

    def place(leaf, sp):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.device_put(leaf, NamedSharding(plan.mesh, sp))
        return leaf

    return jax.tree_util.tree_map(place, state, spec,
                                  is_leaf=lambda x: x is None)
