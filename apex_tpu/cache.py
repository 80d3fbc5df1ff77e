"""Warm-start engine: persistent compilation cache + AOT warmup (ISSUE 7).

Two cold-start taxes keep the examples' steady state from beginning at
step 1 (r05: imagenet 1530 img/s steady vs 2492 best-window, and the
``--prof`` best-window probes each pay fresh compiles):

* the **first-run compile** — tens of seconds of XLA backend work that
  re-runs on every process start even though nothing changed;
* the **step-0 trace+compile inside the timed loop** — the
  :class:`~apex_tpu.runtime.StepPipeline` device loop compiles on its
  first dispatch (and re-specializes on call 1 when the donated state
  returns with the mesh sharding), so the steady clock must exclude the
  first two calls.

This module removes both:

* :func:`enable` turns on jax's **persistent compilation cache** (an
  on-disk executable store keyed by HLO fingerprint): the second process
  start deserializes instead of recompiling — cold compiles are paid
  once per (program, jaxlib), not once per run.
* :func:`warmup` **AOT-compiles** a pipeline's device loop for the
  declared ``(K, shape)`` signatures BEFORE step 0 —
  ``jit(...).lower(shapes).compile()`` on abstract
  ``ShapeDtypeStruct``s, so no real data, no real step, no state
  mutation.  :meth:`StepPipeline.warmup
  <apex_tpu.runtime.StepPipeline.warmup>` stores the compiled
  executable and dispatches straight to it, bypassing the jit tracing
  machinery entirely: with a warm cache there are ZERO compiles (and
  zero traces) after step 0, which
  :func:`apex_tpu.prof.assert_trace_count` can pin.

Usage::

    import apex_tpu.cache
    apex_tpu.cache.enable()      # once, at startup; see resolve_dir

    pipe = runtime.StepPipeline(step_fn, k, ...)
    pipe.warmup(state, window)          # AOT: compile before step 0
    for window, n in windows:
        state, metrics = pipe.step_window(state, window, n)   # no compiles
"""

from __future__ import annotations

import os
from typing import Any, Optional, Tuple

import jax

from .telemetry import events as _events

__all__ = ["enable", "resolve_dir", "is_enabled", "cache_dir",
           "abstractify", "signature", "warmup"]

_STATE = {"dir": None}

#: set from outside to place the cache (jax reads it into
#: ``jax_compilation_cache_dir`` at import); always wins over code
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: where the cache goes otherwise: a fixed path inside the checkout (the
#: directory is part of the cache key, so a path made from a temp dir,
#: a uid, a pid or the time would never hit)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def resolve_dir(path: Optional[str] = None) -> str:
    """THE decision of where the persistent compilation cache lives:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else an explicit ``path``,
    else ``<checkout>/.jax_cache``.  Everything that wants a cache
    (``benchmark/run.py``, ``chip_smoke.py``, the examples) goes through
    :func:`enable`, which asks here."""
    env = os.environ.get(ENV_VAR)
    return os.path.abspath(os.path.expanduser(
        env or path or _DEFAULT_DIR))


def enable(path: Optional[str] = None, *,
           min_entry_size_bytes: int = -1,
           min_compile_time_secs: float = 0.0) -> str:
    """Enable jax's persistent compilation cache at
    :func:`resolve_dir(path) <resolve_dir>`.

    Creates the directory, points ``jax_compilation_cache_dir`` at it
    (a no-op when the environment variable already did) and drops the
    size/compile-time floors (both default to "cache everything": a
    train-step executable is always worth keeping; the defaults exist
    to keep tiny one-off programs out of shared caches).  Idempotent;
    returns the resolved directory.
    """
    path = resolve_dir(path)
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        # The backend binds its store on first use; re-pointing the
        # config alone would silently keep writing to the old dir.
        from jax._src import compilation_cache as _cci
        _cci.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      min_entry_size_bytes)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_time_secs)
    # The kernel autotuner's per-device config cache (ISSUE 14) lives
    # beside the compiled-executable store: one cache directory holds
    # both halves of warm start — programs AND the block configs the
    # programs were built with.
    from .tune import store as _tune_store
    _tune_store.set_default_dir(path)
    _STATE["dir"] = path
    return path


def is_enabled() -> bool:
    return _STATE["dir"] is not None


def cache_dir() -> Optional[str]:
    """The directory :func:`enable` installed (None when disabled)."""
    return _STATE["dir"]


def abstractify(tree):
    """Pytree of ``ShapeDtypeStruct``s mirroring ``tree``'s arrays —
    shape, dtype AND sharding (jit specializes on all three; dropping
    the sharding would AOT-compile a program the real dispatch then
    can't use).  Non-array leaves (plain ints/bools) pass through and
    specialize the compile exactly like a real call."""
    def one(leaf):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return leaf        # caller-declared template (sharding kept)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            # Pin only COMMITTED placements (device_put with an explicit
            # sharding — e.g. a mesh-staged batch window).  Uncommitted
            # arrays (fresh init output on the default device) must stay
            # unconstrained: pinning their incidental single-device
            # sharding next to a mesh-sharded window is a device-set
            # conflict at lower(), and the partitioner's free choice is
            # exactly what the real call gets.
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and getattr(leaf, "committed", False):
                return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                            sharding=sharding)
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map(one, tree)


def signature(tree, limit: int = 16, *,
              static: Tuple = ()) -> Tuple[str, ...]:
    """Shape/dtype signature of a pytree's leading leaves — the AOT
    executable lookup key (matches the retrace-event signature the
    runtime emits, so telemetry and warmup agree on what "same window"
    means).

    ``static`` appends static parameters — ints/strs that specialize
    the compile but are not array leaves (ISSUE 11 satellite: the
    serving engine's sequence-length buckets) — so per-bucket
    executables key cleanly into one AOT table: two calls whose array
    signatures collide but whose bucket differs get distinct keys, and
    a bucket never warmed is a clean lookup MISS (the caller's jit
    fallback path), not a wrong-executable dispatch."""
    leaves = jax.tree_util.tree_leaves(tree)
    sig = tuple(f"{getattr(l, 'dtype', type(l).__name__)}"
                f"{list(getattr(l, 'shape', ()))}"
                for l in leaves[:limit])
    if static:
        sig = sig + tuple(f"static:{v!r}" for v in static)
    return sig


def warmup(jitted, *args) -> Any:
    """AOT-compile ``jitted`` (a ``jax.jit`` callable) for ``args``'
    signature: ``lower().compile()`` over :func:`abstractify`-ed
    arguments.  Nothing executes and nothing is donated — ``args`` may
    be live training state.  Returns the compiled executable; call it
    with concrete arrays of the same signature to bypass tracing
    entirely.  With the persistent cache :func:`enable`-d, the backend
    compile inside is itself a disk hit on the second process start.

    With a telemetry recorder active, one ``warmup`` event says what the
    three stages cost and whether the persistent cache held the program
    (:func:`apex_tpu.telemetry.events.warmup_begins`).  The stages are
    told apart by the recorder's ``jax.monitoring`` listener, not by
    code here, and this frame holds nothing but its arguments, on
    purpose.  Tracing and lowering recurse a hundred frames deep, and
    CPython 3.12 keeps frames in 16 KiB chunks that it unmaps and maps
    again for every call that straddles a chunk's end; which calls
    straddle follows from the size of every frame below.  Twelve more
    locals here took ResNet-50's lowering from 1.2 s to 17 to 36 s on
    the chip, the same program (``PERF.md`` section 6, PR 35).  Until
    the warm-up is made immune to that (``ROADMAP.md`` S13), this frame
    keeps the size it had: ``tests/test_setup_telemetry.py`` holds it to
    the one-liner's.
    """
    _events.warmup_begins(jitted)
    try:
        return jitted.lower(*abstractify(args)).compile()
    finally:
        _events.warmup_ends()
