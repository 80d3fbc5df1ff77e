"""``amp.scale_loss`` — the backward context, imperative API.

TPU-native equivalent of reference ``apex/amp/handle.py:13-155``.  In JAX
gradients come from ``jax.grad`` rather than ``.backward()`` side effects, so
the context manager yields a scaled loss value and the user delivers the
gradients of that scaled loss to the optimizer inside the block::

    loss, grads = optimizer.value_and_grad(loss_fn)(batch)   # grads pre-scaled
    with amp.scale_loss(loss, optimizer) as scaled_loss:
        optimizer.backward(grads)
    optimizer.step()

On exit the context runs each optimizer's ``_post_amp_backward`` (unscale
bf16 grads into fp32 master grads — reference ``_process_optimizer.py:
153-194``), updates the loss scale, and on overflow arms a one-shot skip of
``optimizer.step`` (reference ``handle.py:126-151`` patches ``step``; here the
optimizer holds a ``_skip_next_step`` latch that restores itself after one
step).

The fully-jitted path does not use this context at all — see
``apex_tpu.training.make_train_step`` where scaling, unscale, scale update and
the masked (skip-aware) optimizer update compile into one XLA program.
"""

from __future__ import annotations

import contextlib

from ._amp_state import _amp_state, maybe_print
from . import autocast


@contextlib.contextmanager
def scale_loss(loss,
               optimizers,
               loss_id: int = 0,
               model=None,
               delay_unscale: bool = False,
               delay_overflow_check: bool = False):
    """Scale ``loss`` by the current loss scale and manage the unscale /
    scale-update / skip-step epilogue.

    ``delay_unscale`` / ``delay_overflow_check`` support gradient
    accumulation exactly like the reference (only unscale+update on the final
    micro-batch).
    """
    if _amp_state.opt_properties is None or not _amp_state.opt_properties.enabled:
        yield loss
        return

    if isinstance(optimizers, (list, tuple)):
        opt_list = list(optimizers)
    else:
        opt_list = [optimizers]

    loss_scaler = _amp_state.loss_scalers[loss_id]

    for opt in opt_list:
        if hasattr(opt, "_prepare_amp_backward"):
            opt._prepare_amp_backward()

    yield loss_scaler.scale_loss(loss)

    if delay_unscale:
        # Grad accumulation: leave scaled grads stashed (reference
        # handle.py:103-108 commentary); nothing else to do this micro-step.
        return

    for opt in opt_list:
        if hasattr(opt, "_post_amp_backward"):
            opt._post_amp_backward(loss_scaler)

    if not delay_overflow_check:
        # The scale state machine updates on device NOW; the host READ of
        # the overflow flag is deferred to each optimizer's step(), which
        # batches all pending scalers' flags into one transfer (the
        # reference reads per scaler, scaler.py:199-200; here each read
        # is a pipeline drain).  Optimizers
        # without the deferral hook fall back to an immediate read.
        flag = loss_scaler.update_scale_deferred()
        if flag is not None:
            deferrable = all(hasattr(opt, "_note_pending_overflow")
                             for opt in opt_list)
            if deferrable:
                for opt in opt_list:
                    opt._note_pending_overflow(flag, loss_id)
            else:
                # Any optimizer without the deferral hook forces a read
                # NOW — and once the flag is on the host there is nothing
                # left to batch, so arm the hooked optimizers eagerly too
                # rather than paying a second read at their step().
                import jax

                if bool(jax.device_get(flag)):  # jaxlint: disable=J001 -- fallback for optimizers without the deferral hook: the flag must be host-side NOW to arm the skip latch
                    for opt in opt_list:
                        if hasattr(opt, "_arm_skip_step"):
                            opt._arm_skip_step()
                    maybe_print(
                        "Gradient overflow.  Skipping step, loss scaler "
                        "{} reducing loss scale to {}".format(
                            loss_id, loss_scaler.loss_scale()))

    # Weight-cast cache dropped once per iteration (reference handle.py:153-155).
    autocast.clear_cast_cache()


# Re-export for `from apex_tpu.amp import disable_casts` parity.
disable_casts = autocast.disable_casts


class AmpHandle:
    """Legacy handle API (reference ``handle.py:167-270``)."""

    def __init__(self, loss_scale="dynamic", enable_caching=True, verbose=False):
        self._enable_caching = enable_caching
        self._verbose = verbose
        from .loss_scaler import LossScaler
        self._loss_scaler = LossScaler(loss_scale)
        self._default_scaler = self._loss_scaler
        self._is_active = True
        self._all_wrappers = []

    def is_active(self):
        return self._is_active

    @contextlib.contextmanager
    def _disable_casts(self):
        with autocast.disable_casts():
            yield

    def wrap_optimizer(self, optimizer, num_loss=1):
        self._default_scaler = None
        from .opt import OptimWrapper
        return OptimWrapper(optimizer, self, num_loss)

    @contextlib.contextmanager
    def scale_loss(self, loss, optimizer):
        if not self.is_active():
            yield loss
            return
        yield self._loss_scaler.scale_loss(loss)
        if hasattr(optimizer, "_post_amp_backward"):
            optimizer._post_amp_backward(self._loss_scaler)
        self._loss_scaler.update_scale_sync()
        if not self._enable_caching:
            autocast.clear_cast_cache()

    @property
    def loss_scale(self):
        return self._loss_scaler.loss_scale()

    def _clear_cache(self):
        autocast.clear_cast_cache()

    def _deactivate(self):
        self._is_active = False


class NoOpHandle:
    def is_active(self):
        return False

    @contextlib.contextmanager
    def _disable_casts(self):
        yield

    def wrap_optimizer(self, optimizer, num_loss=1):
        return optimizer

    @contextlib.contextmanager
    def scale_loss(self, loss, optimizer):
        yield loss

    @property
    def loss_scale(self):
        return 1.0

    def _deactivate(self):
        pass
