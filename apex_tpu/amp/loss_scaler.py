"""Loss scaling: static and dynamic, as jit-safe functional state.

TPU-native re-design of the reference scaler (``apex/amp/scaler.py:33-217``).
The semantics preserved exactly:

* dynamic: init 2**16 (capped by ``max_loss_scale`` default 2**24), doubled
  every ``scale_window`` (2000) clean steps, halved on overflow, optional
  ``min_loss_scale`` floor (reference ``scaler.py:38-56, 197-217``).
* ``unscale`` divides grads by the scale and raises a *device-side* overflow
  flag if any grad is non-finite (reference multi_tensor_scale writes a GPU
  int buffer; here the flag is a traced jnp scalar — zero host syncs unless
  the caller asks for one).
* per-loss scalers (``num_losses``/``loss_id``) and ``state_dict`` fields
  ``loss_scale`` + ``unskipped`` round-trip (reference ``frontend.py:361-400``).

TPU-first difference: because the default half type is bfloat16 (fp32 exponent
range), the default loss scale is **static 1.0** — the whole state machine then
compiles away to a no-op.  The dynamic machine is fully functional for fp16
users and for checkpoint parity.

The class is registered as a pytree so a ``LossScalerState`` can live inside a
jitted train step: ``update_scale`` is pure (returns a new state) and the
"skip step" decision is a traced boolean the optimizer consumes as a mask —
no data-dependent Python control flow (reference ``handle.py:126-151`` patches
``optimizer.step``; the TPU equivalent is a select, see
``apex_tpu/optimizers``).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .. import multi_tensor as mta


class LossScalerState(NamedTuple):
    """Traced state of one loss scaler (a valid jit carry)."""
    loss_scale: jnp.ndarray      # f32 scalar
    unskipped: jnp.ndarray       # i32 scalar — clean steps since last overflow
    overflow: jnp.ndarray        # bool scalar — overflow seen this step


# Imperative-path fast lanes: called OUTSIDE a jitted step, the
# per-leaf unscale/axpby sweeps used to run as ~100 eager dispatches per
# backward, the dominant cost of the DCGAN imperative loop (per-dispatch
# cost on this installation: not measured).
# jit makes each sweep ONE cached program per tree structure; calling
# them during an outer trace is also fine (jit inlines).
@functools.partial(jax.jit, static_argnames=("store",))
def _unscale_fp32(tree, scale, store=None):
    return mta.multi_tensor_scale(tree, 1.0 / scale, out_dtype=jnp.float32,
                                  store=store)


@functools.partial(jax.jit, static_argnames=("store",))
def _axpby_fp32(new, stashed, scale, store=None):
    return mta.multi_tensor_axpby(new, stashed, 1.0 / scale, 1.0,
                                  out_dtype=jnp.float32, store=store)


@functools.lru_cache(maxsize=None)
def _update_scale_lane(dynamic, scale_factor, scale_window,
                       min_loss_scale, max_loss_scale):
    """One compiled update-scale program per CONFIG (not per scaler
    instance): DCGAN's three identical scalers share a single compile
    instead of paying trace+compile three times."""
    def update(state):
        if not dynamic:
            return state._replace(overflow=jnp.asarray(False))
        overflow = state.overflow
        shrunk = state.loss_scale / scale_factor
        if min_loss_scale is not None:
            shrunk = jnp.maximum(shrunk, min_loss_scale)
        window_full = (state.unskipped + 1) >= scale_window
        grown = jnp.minimum(state.loss_scale * scale_factor,
                            max_loss_scale)
        new_scale = jnp.where(
            overflow, shrunk,
            jnp.where(window_full, grown, state.loss_scale))
        new_unskipped = jnp.where(
            jnp.logical_or(overflow, window_full), 0, state.unskipped + 1)
        return LossScalerState(
            loss_scale=new_scale.astype(jnp.float32),
            unskipped=new_unskipped.astype(jnp.int32),
            overflow=jnp.asarray(False),
        )
    return jax.jit(update)


def all_finite(tree, store=None) -> jnp.ndarray:
    """Device-side AND-reduction of isfinite over a grad tree (no host
    sync); with ``store`` (or a Packed tree), one reduce per bucket."""
    return mta.tree_finite(tree, store=store)


class LossScaler:
    """Static or dynamic loss scaler.

    Functional usage (the idiomatic path — everything stays on device)::

        scaler = LossScaler("dynamic")
        state = scaler.init()
        ...inside jit...
        loss = scaler.scale_loss(loss, state)
        grads, state = scaler.unscale(grads, state)   # sets state.overflow
        state = scaler.update_scale(state)            # adjust scale, reset flag
        # optimizer consumes state.overflow as a skip mask

    Imperative usage (API parity with the reference) keeps an internal state
    and exposes ``loss_scale()`` / ``update_scale()`` like
    ``apex/amp/scaler.py``.
    """

    warned_unscaling_non_fp32_grad = False

    def __init__(self,
                 loss_scale,
                 init_scale=2.**16,
                 scale_factor=2.,
                 scale_window=2000,
                 min_loss_scale=None,
                 max_loss_scale=2.**24):
        if loss_scale == "dynamic":
            self.dynamic = True
            self._initial_scale = min(max_loss_scale, init_scale)
        else:
            self.dynamic = False
            self._initial_scale = float(loss_scale)
        self._scale_factor = scale_factor
        self._scale_window = scale_window
        self._min_loss_scale = min_loss_scale
        self._max_loss_scale = max_loss_scale
        self._imp_steps = 0      # imperative update count (telemetry)
        self._state = self.init()

    # -- functional core -----------------------------------------------------
    def init(self) -> LossScalerState:
        return LossScalerState(
            loss_scale=jnp.float32(self._initial_scale),
            unskipped=jnp.int32(0),
            overflow=jnp.asarray(False),
        )

    def scale_loss(self, loss, state: LossScalerState = None):
        state = self._state if state is None else state
        if not self.dynamic and self._initial_scale == 1.0:
            return loss  # fast path, reference handle.py:93-102
        return jnp.asarray(loss, jnp.float32) * state.loss_scale

    def unscale(self, grads, state: LossScalerState = None, *, scale=None,
                store=None):
        """Divide grads by the scale; record overflow in the returned state.

        Equivalent of ``LossScaler.unscale`` → multi_tensor_scale with the
        device-side noop flag (reference ``scaler.py:57-117``).  Grads are
        unscaled in fp32 (master-grad dtype).

        ``store`` (a :class:`~apex_tpu.multi_tensor.BucketStore`) routes
        the sweep and the overflow check through flat buckets — one
        ``isfinite``+reduce per bucket instead of per leaf; a ``Packed``
        ``grads`` value stays packed in the output.
        """
        explicit = state is not None
        state = self._state if state is None else state
        s = state.loss_scale if scale is None else scale
        out, overflow = _unscale_fp32(grads, s, store=store)
        if self.dynamic:
            new_state = state._replace(overflow=jnp.logical_or(state.overflow, overflow))
        else:
            new_state = state
        if not explicit:
            self._state = new_state
        return out, new_state

    def unscale_with_stashed(self, new_grads, stashed_grads,
                             state: LossScalerState = None, *, scale=None,
                             store=None):
        """Gradient accumulation: out = new/scale + stashed, overflow-checked.

        Equivalent of the fused axpby path (reference ``scaler.py:152-189``);
        ``store`` routes it through flat buckets.
        """
        explicit = state is not None
        state = self._state if state is None else state
        s = state.loss_scale if scale is None else scale
        out, overflow = _axpby_fp32(new_grads, stashed_grads, s, store=store)
        if self.dynamic:
            new_state = state._replace(overflow=jnp.logical_or(state.overflow, overflow))
        else:
            new_state = state
        if not explicit:
            self._state = new_state
        return out, new_state

    def clear_overflow_state(self, state: LossScalerState = None):
        explicit = state is not None
        state = self._state if state is None else state
        new_state = state._replace(overflow=jnp.asarray(False))
        if not explicit:
            self._state = new_state
        return new_state

    def update_scale(self, state: LossScalerState = None):
        """Adjust the scale from the overflow flag; pure and traceable
        (the compiled state machine is shared per config, see
        :func:`_update_scale_lane` — the eager jnp.where chain was ~6
        dispatches + a host->device upload of the False constant per
        call).

        Reference ``scaler.py:197-217``: on overflow, scale/2 (clamped at
        ``min_loss_scale``) and reset the window; every ``scale_window`` clean
        steps, scale*2 (clamped at ``max_loss_scale``).
        """
        explicit = state is not None
        state = self._state if state is None else state
        fn = _update_scale_lane(self.dynamic, self._scale_factor,
                                self._scale_window, self._min_loss_scale,
                                self._max_loss_scale)
        new_state = fn(state)
        if not explicit:
            self._state = new_state
        return new_state

    # -- imperative / checkpoint API (reference parity) ----------------------
    def loss_scale(self):
        return float(jax.device_get(self._state.loss_scale))  # jaxlint: disable=J001 -- imperative API parity (reference scaler.py loss_scale()); jitted paths read state.loss_scale on device

    def update_scale_sync(self) -> bool:
        """Imperative update: ONE host sync per step, like the reference's
        ``overflow_buf.item()`` (``scaler.py:199-200``).  Returns
        ``should_skip`` for the step-skipping contract."""
        should_skip = bool(jax.device_get(self._state.overflow)) and self.dynamic  # jaxlint: disable=J001 -- the documented ONE sync per imperative step (reference overflow_buf.item()); prefer update_scale_deferred to batch it
        self._state = self.update_scale(self._state)
        self._imp_steps += 1
        if should_skip:
            # Telemetry (ISSUE 5): the imperative twin of the scale
            # events the recorder derives from fetched window metrics on
            # the functional path.  The overflow flag was just read
            # above — no extra sync.
            from .. import telemetry as _telemetry
            rec = _telemetry.get_recorder()
            if rec is not None:
                rec.metrics.counter("loss_scale_skips").inc()
                rec.event("scale", event="skip", step=self._imp_steps - 1,
                          source="imperative")
        return should_skip

    def update_scale_deferred(self):
        """Imperative update with the host read DEFERRED: runs the same
        device-side scale state machine as :meth:`update_scale_sync` but
        returns the pre-update overflow flag as a DEVICE scalar (or None
        for static scalers, which never skip) instead of reading it.

        The caller batches the reads —
        ``FusedOptimizer._resolve_pending_overflows`` (``optimizers/
        base.py``, called from ``step``) stacks every pending scaler's
        flag into ONE device->host transfer, so a multi-loss iteration
        (e.g. DCGAN's three scalers) pays one round-trip per optimizer
        step instead of one per scaler (each read drains the dispatch
        pipeline).  Skip/step decisions are
        bit-identical to the sync path — only WHEN the host learns the
        flag changes."""
        flag = self._state.overflow if self.dynamic else None
        self._state = self.update_scale(self._state)
        self._imp_steps += 1
        return flag

    @property
    def state(self) -> LossScalerState:
        return self._state

    @state.setter
    def state(self, s: LossScalerState):
        self._state = s

    def state_dict(self):
        """Reference serializes ``loss_scale`` + ``unskipped``
        (``frontend.py:361-370``)."""
        return {"loss_scale": float(jax.device_get(self._state.loss_scale)),
                "unskipped": int(jax.device_get(self._state.unskipped))}

    def load_state_dict(self, sd):
        self._state = LossScalerState(
            loss_scale=jnp.float32(sd["loss_scale"]),
            unskipped=jnp.int32(sd["unskipped"]),
            overflow=jnp.asarray(False))
