"""Fully-jitted amp training steps — the TPU-idiomatic path.

The reference's training iteration is an imperative choreography of hooks and
patched methods (SURVEY.md §3.2).  On TPU the whole iteration — input cast,
bf16 forward, backward, gradient all-reduce, unscale + overflow flag, the
loss-scale state machine, and the skip-masked optimizer update — compiles
into ONE XLA program.  ``make_train_step`` builds that program from the same
opt-level semantics as ``amp.initialize``:

* O0: fp32 end to end.
* O1: autocast policy active inside the traced loss (enable via
  ``amp.init()``); params fp32.
* O2: params stored ONCE as fp32 masters; the bf16 model copy exists only
  *inside* the step (cast at trace time, keep-norm-fp32 honored) — this is
  the master-weights design with zero duplicate storage, the TPU-first
  answer to ``_process_optimizer``'s master machinery.
* O3: params stored bf16, no masters.
* O4: EXACTLY O2's storage/scaling semantics; the int8 matmul routing is
  a property of the MODEL (the ``quant=`` hook of ``apex_tpu.models`` +
  ``apex_tpu.quant``, ISSUE 13) — a model without frozen calibration
  runs bitwise as O2.

Step skipping is a device-side select (``apply_mask``), so dynamic loss
scaling costs no host sync at all (the reference pays one D2H per step,
``scaler.py:199-200``).

Usage::

    tx = apex_tpu.training.adam(lr=1e-3)
    init_fn, step_fn = make_train_step(loss_fn, tx, opt_level="O2",
                                       axis_name="data")
    state = init_fn(params)
    state, metrics = jax.jit(step_fn)(state, batch)       # single chip
    # or shard_map(step_fn, mesh, ...) for DP over a mesh axis
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .amp import policy as _policy
from .amp.loss_scaler import LossScaler, LossScalerState
from .amp.properties import opt_levels
from .optimizers import functional as F
from .parallel.distributed import reduce_gradients

#: The phases of a training step, as ``jax.named_scope``s around the work
#: ``make_train_step`` issues.  This is the operator's vocabulary: XProf and
#: TensorBoard show these names in the framework-op view of any trace of any
#: apex_tpu training step, and the compiled HLO carries them in ``op_name``.
#: The backward pass has no scope of its own: JAX renders the transposed
#: equations of ``apex.forward`` as ``transpose(jvp(apex.forward))`` (and the
#: gradient up-cast as ``transpose(jvp(apex.cast))``).  Scopes are metadata:
#: they change no compiled program.
PHASE_SCOPES = ("apex.cast", "apex.forward", "apex.allreduce", "apex.scaler",
                "apex.optimizer", "apex.metrics")
(_CAST, _FORWARD, _ALLREDUCE, _SCALER, _OPTIMIZER, _METRICS) = PHASE_SCOPES


def _pmean_varying(x, axis_name):
    """pmean over only the axes ``x`` actually varies on (pmean over an
    invarying axis is rejected by shard_map's vma checking — and would be
    the identity anyway)."""
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    vma = jax.typeof(x).vma
    names = tuple(a for a in names if a in vma)
    if names:
        return jax.lax.pmean(x, names)
    return x


def _por_varying(flag, axis_name):
    """Logical OR of a bool scalar over the mesh axes it varies on.  With
    tensor-parallel (sharded) gradients each shard sees only its slice, so
    the overflow flag must be agreed mesh-wide or the scaler state — and
    then the parameters — would diverge across ranks.

    Under shard_map the flag's vma names EVERY axis it varies on — e.g.
    "tp" even when the caller only reduces grads over ("data",) — so the
    vma, when available, wins over ``axis_name``.  Without vma the
    ``axis_name`` list is used as-is: psum of an already-replicated flag
    over an extra axis is ``n * flag``, and the ``> 0`` turns either form
    into the OR.
    """
    from .parallel.distributed import vma_tracking_live

    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    # Trust an empty vma only when vma tracking is actually live on this
    # trace: under shard_map(check_vma=False) every aval reports an empty
    # vma, which must NOT be read as "already replicated".
    if names and vma_tracking_live(names[0]):
        names = tuple(jax.typeof(flag).vma)
    if names:
        return jax.lax.psum(flag.astype(jnp.int32), names) > 0
    return flag


class FunctionalOptimizer(NamedTuple):
    init: Callable
    update: Callable      # (grads, state, params, lr, grad_scale, apply_mask)
    # Declared capability, not inferred: True iff ``update`` treats every
    # parameter element independently (no per-tensor norms / trust ratios),
    # so it remains correct on arbitrary flat chunks of the parameter
    # vector.  ``parallel.zero.zero1`` requires it; third-party optimizers
    # must opt in explicitly — the conservative default keeps unknown
    # optimizers out of chunk-sharded paths.
    elementwise: bool = False


def _bucketed_tx(init_fn, update_fn, *, elementwise) -> FunctionalOptimizer:
    """FunctionalOptimizer over the flat-bucket engine (ISSUE 4): the
    BucketStore is built lazily from the first ``init(params)`` call (a
    static shape/dtype read — safe under jit tracing), and the optimizer
    state lives as a few large ``Packed`` buffers, so a ``lax.scan``
    carry (``runtime.StepPipeline`` K-step device loops) holds O(buckets)
    moment arrays instead of two per parameter leaf."""
    cell = {}

    def _store(params):
        from .multi_tensor.buckets import cached_store
        return cached_store(cell, params)

    def init(params):
        return init_fn(params, store=_store(params))

    def update(grads, state, params, **kw):
        return update_fn(grads, state, params, store=_store(params), **kw)

    return FunctionalOptimizer(init, update, elementwise=elementwise)


def adam(lr=1e-3, *, bucketed=False, **kw) -> FunctionalOptimizer:
    if bucketed:
        return _bucketed_tx(F.adam_init,
                            functools.partial(F.adam_update, lr=lr, **kw),
                            elementwise=True)
    return FunctionalOptimizer(
        F.adam_init, functools.partial(F.adam_update, lr=lr, **kw),
        elementwise=True)


def sgd(lr=1e-3, momentum=0.0, *, bucketed=False, **kw) -> FunctionalOptimizer:
    if bucketed:
        return _bucketed_tx(
            functools.partial(F.sgd_init, momentum=momentum),
            functools.partial(F.sgd_update, lr=lr, momentum=momentum, **kw),
            elementwise=True)
    return FunctionalOptimizer(
        functools.partial(F.sgd_init, momentum=momentum),
        functools.partial(F.sgd_update, lr=lr, momentum=momentum, **kw),
        elementwise=True)


def lamb(lr=1e-3, *, bucketed=False, **kw) -> FunctionalOptimizer:
    if bucketed:
        return _bucketed_tx(F.lamb_init,
                            functools.partial(F.lamb_update, lr=lr, **kw),
                            elementwise=False)
    return FunctionalOptimizer(
        F.lamb_init, functools.partial(F.lamb_update, lr=lr, **kw))


def novograd(lr=1e-3, *, bucketed=False, **kw) -> FunctionalOptimizer:
    if bucketed:
        return _bucketed_tx(
            F.novograd_init,
            functools.partial(F.novograd_update, lr=lr, **kw),
            elementwise=False)
    return FunctionalOptimizer(
        F.novograd_init, functools.partial(F.novograd_update, lr=lr, **kw))


class TrainState(NamedTuple):
    """Carry of the jitted step.  ``params`` is the single source of truth:
    fp32 for O0/O1/O2/O4 (O2/O4 cast inside the step), bf16 for O3."""
    params: Any
    opt_state: Any
    scaler: LossScalerState
    model_state: Any      # batch_stats etc; None if unused


def chain_steps(step_fn: Callable) -> Callable:
    """Device loop: K train steps as ONE compiled program.

    ``chain_steps(step_fn)(state, batches)`` runs ``lax.scan`` of the step
    over ``batches`` (every leaf stacked on a leading K axis — a
    pre-staged pool, like a prefetching input pipeline's lookahead) and
    returns ``(state, metrics)`` with per-step metrics stacked.

    This is the standard TPU training-loop shape: host dispatch costs are
    paid once per PROGRAM, not per step, so chaining K steps amortizes
    them by K: a fixed cost per jitted call plus a cost per argument leaf
    (a ResNet-50 TrainState is ~430 leaves; neither is measured on the
    current installation — see PERF.md).  Cf. steps_per_execution in
    other TPU frameworks.  The jitted-per-step path stays the right
    choice when the host must see metrics every step (e.g. imperative
    loops).

    Donate BOTH the carried state and the consumed window: the stacked
    batch buffer is K full batches of HBM (2.4 GB at K=32, b128, 224px)
    and without donation it stays pinned for the whole call — donating
    it lets XLA release/reuse that memory while the loop still runs, so
    the next staged window's H2D never doubles peak footprint.  A
    donated window is consumed: build a FRESH stack per call (a reused
    pool must not donate).  :class:`apex_tpu.runtime.StepPipeline` wraps
    this pattern — windows staged through the prefetcher, ragged tails,
    deferred metric reads — for the user-facing training path.

    Usage::

        chained = jax.jit(chain_steps(step_fn), donate_argnums=(0, 1))
        batches = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *pool)            # pool -> [K, ...]
        state, metrics = chained(state, batches)         # K real steps
    """
    def chained(state, batches):
        return jax.lax.scan(step_fn, state, batches)
    return chained


def make_train_step(loss_fn: Callable,
                    optimizer: FunctionalOptimizer,
                    *,
                    opt_level: str = "O2",
                    loss_scale=None,
                    keep_batchnorm_fp32: Optional[bool] = None,
                    cast_model_type=None,
                    axis_name: Optional[str] = None,
                    reduce_grads: bool = True,
                    accum_steps: int = 1,
                    gradient_average: bool = True,
                    gradient_predivide_factor: float = 1.0,
                    allreduce_always_fp32: bool = False,
                    axis_index_groups=None,
                    norm_predicate=None,
                    has_model_state: bool = False,
                    scale_window: int = 2000,
                    min_loss_scale=None,
                    max_loss_scale: float = 2.**24,
                    param_view: Optional[Callable] = None):
    """Build ``(init_fn, step_fn)`` for one amp training step.

    ``loss_fn(params, model_state, batch) -> (loss, new_model_state)`` when
    ``has_model_state`` else ``loss_fn(params, batch) -> loss``.  Inside the
    step, ``params`` arrive already cast to the compute dtype per opt level.

    ``reduce_grads=False`` keeps ``axis_name`` driving the mesh-wide
    overflow agreement and the metric pmean but skips the DDP gradient
    all-reduce — for optimizers that own the reduction themselves
    (``parallel.zero.zero1`` reduce-scatters inside ``update``).

    ``param_view`` maps the STORED parameter pytree to the tree
    ``loss_fn`` consumes, INSIDE the differentiated function — so its
    transpose runs in the backward and the optimizer sees gradients in
    the stored layout.  This is the ZeRO-3 hook
    (``apex_tpu.parallel.mesh``): the stored params are sharded flat
    buckets, the view all-gathers and unpacks them, and autodiff
    transposes the gather into exactly the reduce-scatter a ZeRO
    optimizer wants — per-bucket, so chunked stores overlap the
    collectives with the surrounding compute.  The opt-level compute
    cast applies AFTER the view (on the full tree, normal O2
    semantics).  Under ``accum_steps > 1`` the view is hoisted out of
    the microbatch scan alongside the cast — one gather per step, not
    per microbatch.  Default: identity.

    ``accum_steps=N`` is gradient accumulation compiled INTO the step —
    the jitted analog of the reference's ``delay_unscale`` micro-batch
    loop (``handle.py`` grad-accumulation contract): every array in
    ``batch`` is split into N microbatches along its leading axis, a
    ``lax.scan`` accumulates the mean of the scaled gradients (model
    state threads through sequentially, like N real steps), and the
    unscale / overflow check / reduction / update run ONCE on the
    accumulated gradients.  Peak activation memory drops by ~N; the
    result matches the full-batch step exactly for batch-size-invariant
    losses (mean-reduced, no cross-microbatch batch stats).
    """
    props = opt_levels[opt_level]()
    if loss_scale is not None:
        props.loss_scale = loss_scale
    if keep_batchnorm_fp32 is not None:
        props.keep_batchnorm_fp32 = keep_batchnorm_fp32
    if cast_model_type is not None:
        props.cast_model_type = cast_model_type

    scaler = LossScaler(props.loss_scale, scale_window=scale_window,
                        min_loss_scale=min_loss_scale,
                        max_loss_scale=max_loss_scale)
    dynamic = scaler.dynamic

    cast_dtype = props.cast_model_type
    cast_in_step = (cast_dtype is not None
                    and jnp.dtype(cast_dtype) != jnp.dtype(jnp.float32)
                    and props.master_weights)
    store_dtype_cast = (cast_dtype is not None
                        and jnp.dtype(cast_dtype) != jnp.dtype(jnp.float32)
                        and not props.master_weights)
    keep_bn = props.keep_batchnorm_fp32
    keep_bn = True if keep_bn is None else keep_bn

    view = param_view if param_view is not None else (lambda p: p)

    def cast_only(params):
        if cast_in_step:
            return _policy.convert_params(params, cast_dtype,
                                          keep_norm_fp32=keep_bn,
                                          norm_predicate=norm_predicate)
        return params

    def compute_cast(params):
        return cast_only(view(params))

    def init_fn(params, model_state=None):
        if store_dtype_cast:  # O3: store reduced precision, no masters
            params = _policy.convert_params(params, cast_dtype,
                                            keep_norm_fp32=keep_bn,
                                            norm_predicate=norm_predicate)
        return TrainState(params=params,
                          opt_state=optimizer.init(params),
                          scaler=scaler.init(),
                          model_state=model_state)

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def step_fn(state: TrainState, batch):
        def scaled_loss_cp(cp, ms, mb):
            with jax.named_scope(_FORWARD):
                if has_model_state:
                    loss, new_ms = loss_fn(cp, ms, mb)
                else:
                    loss = loss_fn(cp, mb)
                    new_ms = ms
                return (jnp.asarray(loss, jnp.float32)
                        * state.scaler.loss_scale), (loss, new_ms)

        def scaled_loss(p, ms, mb):
            with jax.named_scope(_CAST):
                cp = compute_cast(p)
            return scaled_loss_cp(cp, ms, mb)

        if accum_steps == 1:
            grads, (loss, new_ms) = jax.grad(
                scaled_loss, has_aux=True)(state.params, state.model_state,
                                           batch)
        else:
            for leaf in jax.tree_util.tree_leaves(batch):
                if leaf.shape[0] % accum_steps:
                    raise ValueError(
                        f"batch leading dim {leaf.shape[0]} not divisible "
                        f"by accum_steps={accum_steps}")
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)

            # The O2/O3 compute cast is hoisted OUT of the scan (one
            # whole-tree cast per step, not per microbatch).  Its
            # transpose is an upcast, which is the identity on the fp32
            # accumulator — so the mean gradient w.r.t. the cast params
            # IS the master gradient.  The param_view is hoisted the
            # same way, but its transpose (the ZeRO-3 reduce-scatter)
            # is NOT the identity: jax.vjp stages it once so the
            # accumulated full-tree gradient is mapped back to the
            # stored layout after the scan — one gather and one scatter
            # per step, not per microbatch.
            with jax.named_scope(_CAST):
                if param_view is not None:
                    full, view_vjp = jax.vjp(view, state.params)
                else:
                    full, view_vjp = state.params, None
                cp = cast_only(full)

            def one_micro(carry, mb):
                ms, g_acc, l_acc = carry
                g, (l, new_ms) = jax.grad(scaled_loss_cp, has_aux=True)(
                    cp, ms, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype) / accum_steps,
                    g_acc, g)
                return (new_ms, g_acc, l_acc + l / accum_steps), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), full)
            (new_ms, grads, loss), _ = jax.lax.scan(
                one_micro, (state.model_state, g0, jnp.float32(0.0)), micro)
            if view_vjp is not None:
                grads, = view_vjp(grads)

        if axis_name is not None and reduce_grads:
            with jax.named_scope(_ALLREDUCE):
                grads = reduce_gradients(
                    grads, axis_name,
                    gradient_average=gradient_average,
                    gradient_predivide_factor=gradient_predivide_factor,
                    allreduce_always_fp32=allreduce_always_fp32,
                    axis_index_groups=axis_index_groups)

        with jax.named_scope(_SCALER):
            grads, scaler_state = scaler.unscale(grads, state.scaler)
            if dynamic and axis_name is not None:
                # Sharded (e.g. tensor-parallel) grads: agree on overflow
                # mesh-wide so every rank skips (or steps) together.
                scaler_state = scaler_state._replace(
                    overflow=_por_varying(scaler_state.overflow, axis_name))
            if dynamic:
                apply_mask = jnp.logical_not(scaler_state.overflow)
            else:
                apply_mask = None
        with jax.named_scope(_OPTIMIZER):
            new_params, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params, apply_mask=apply_mask)
        with jax.named_scope(_SCALER):
            scaler_state = scaler.update_scale(scaler_state)

        with jax.named_scope(_METRICS):
            if axis_name is not None:
                # Replicated metric, like the reference examples' allreduced
                # loss prints (main_amp.py:356-394); batch stats (BN running
                # mean/var) averaged across replicas so the carried state
                # stays replicated — the reference leaves stats per-rank,
                # which only works because each rank owns its module copy;
                # under SPMD a replicated pytree is the contract.  Each
                # value is averaged only over axes it actually varies on.
                loss = _pmean_varying(loss, axis_name)
                if new_ms is not None:
                    new_ms = jax.tree_util.tree_map(
                        lambda x: _pmean_varying(x, axis_name), new_ms)
            metrics = {"loss": loss,
                       "loss_scale": scaler_state.loss_scale,
                       "overflow": (jnp.logical_not(apply_mask)
                                    if apply_mask is not None
                                    else jnp.asarray(False))}
        return TrainState(params=new_params, opt_state=new_opt_state,
                          scaler=scaler_state, model_state=new_ms), metrics

    return init_fn, step_fn
