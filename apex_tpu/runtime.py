"""Step-pipelining runtime: K-step device loops over staged batch windows.

BENCH r05 measured the gap this module closes: the chip finishes a
ResNet-50 amp-O2 step in 46.9 ms but the per-step jitted wall time is
52.3 ms (~10% pure dispatch), and the flagship examples were far worse
(imagenet held 1529 img/s against a 2492 img/s best window; DCGAN 4.67
it/s against 57).  The reference hides the same class of overhead with
CUDA-stream prefetch (``examples/imagenet/main_amp.py`` ``data_prefetcher``)
and per-step kernel fusion; the TPU-native answer is to make the *program*
— not the step — the unit of host dispatch:

* :class:`StepPipeline` runs K jitted train steps per host dispatch as ONE
  compiled ``lax.scan`` over a stacked ``[K, ...]`` batch window, donating
  both the carried state and the consumed window;
* :func:`stage_windows` groups a per-step batch stream into such windows
  and stages them through :class:`apex_tpu.data.PrefetchLoader`, so the
  host->device transfer of window N+1 overlaps the device loop of window N
  (the ``data_prefetcher`` analog, one level up);
* :class:`DeferredMetrics` holds each window's per-step metrics as DEVICE
  arrays and hands reads back one dispatch behind, so the hot loop never
  blocks on a scalar — by the time window N-1's metrics are fetched,
  window N is already enqueued and the device keeps working through the
  round-trip.

Ragged epoch tails (a final window with fewer than K real batches) and
mid-window dynamic-loss-scale skips are handled WITHOUT retracing: the
tail is padded to the same ``[K, ...]`` shape and executed by a separate
masked program (compiled once, ever) whose per-step carry is select-gated
on a ``valid`` mask, and the scaler's overflow flag never leaves the
device (``multi_tensor`` keeps it a traced scalar).  The hot-window
program therefore compiles exactly once per (K, shape) — pin it with
:func:`apex_tpu.prof.assert_trace_count`.

Usage::

    from apex_tpu import runtime

    pipe = runtime.StepPipeline(step_fn, k=16)
    windows = runtime.stage_windows(batch_stream, k=16,
                                    transform=normalize)
    reader = runtime.DeferredMetrics()
    for window, n_valid in windows:
        state, metrics = pipe.step_window(state, window, n_valid)
        prev = reader.push(metrics, n_valid)
        if prev is not None and want_to_print(prev.step):
            host = prev.fetch()            # one stacked transfer, one
            ...                            # dispatch behind the device

    final = reader.last()                  # drains the pipeline

For SPMD runs pass ``wrap`` — a callable (e.g. a ``shard_map`` partial)
applied to the loop function ``(state, window, valid) -> (state, metrics)``
before ``jax.jit``; the window's leading K axis stays unsharded.
"""

from __future__ import annotations

import signal as _signal
import threading
import time
import warnings
from typing import Any, Callable, Iterable, Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry as _telemetry
from .training import chain_steps

__all__ = ["StepPipeline", "DeferredMetrics", "WindowMetrics",
           "GracefulShutdown", "stage_windows", "window_batches"]


class GracefulShutdown:
    """Preemption drain: SIGTERM/SIGINT request a clean stop at the next
    window boundary (ISSUE 9).

    A fleet preempts with a signal and a deadline; today that signal
    kills the loop mid-window and loses everything since the last
    checkpoint.  Installed around the training loop, this handler turns
    the FIRST signal into a *drain request* the loop polls at each
    window boundary — finish the in-flight window, write the final
    checkpoint, flush the recorder summary and the watchdog health line
    (the examples' ``finally``-flushed recorders already prove that
    half), then exit cleanly.  A SECOND signal escalates to the default
    handling (the operator insists), so a wedged drain can still be
    killed interactively.

    Usage (the examples' default)::

        with runtime.GracefulShutdown() as stop:
            for window, n_valid in windows:
                state, metrics = pipe.step_window(state, window, n_valid)
                if stop.draining:
                    mgr.save(step, state, block=True)   # final checkpoint
                    break

    Thread-safe: the drain flag is a ``threading.Event`` (signals land
    on the main thread; the loop may poll from anywhere).  With a
    telemetry recorder active, the request emits a ``drain`` event
    carrying the signal name.  Outside the main thread (where
    ``signal.signal`` raises), installation degrades to a no-op handler
    set and :meth:`request` remains the programmatic trigger.
    """

    def __init__(self, signals=(_signal.SIGTERM, _signal.SIGINT), *,
                 telemetry=None):
        self.signals = tuple(signals)
        self._telemetry = telemetry
        self._drain = threading.Event()
        self._prev: dict = {}
        self._installed = False
        self.reason: Optional[str] = None

    # -- the flag -----------------------------------------------------------
    @property
    def draining(self) -> bool:
        """True once a drain has been requested (signal or programmatic)."""
        return self._drain.is_set()

    def request(self, reason: str = "programmatic") -> None:
        """Trigger the drain without a signal (tests, schedulers)."""
        first = not self._drain.is_set()
        self.reason = self.reason or reason
        self._drain.set()
        if first:
            rec = (self._telemetry if self._telemetry is not None
                   else _telemetry.get_recorder())
            if rec is not None:
                rec.event("drain", reason=reason)

    # -- signal plumbing ----------------------------------------------------
    def _handler(self, signum, frame):
        del frame
        try:
            name = _signal.Signals(signum).name
        except ValueError:        # pragma: no cover - exotic signum
            name = str(signum)
        if self._drain.is_set():
            # Second signal: the operator insists — restore the previous
            # disposition and re-raise so default handling (KeyboardInterrupt
            # / termination) takes over instead of a wedged drain.
            self.uninstall()
            _signal.raise_signal(signum)
            return
        self.request(f"signal:{name}")

    def install(self) -> "GracefulShutdown":
        """Install the handlers (idempotent).  Returns ``self``."""
        if self._installed:
            return self
        for sig in self.signals:
            try:
                self._prev[sig] = _signal.signal(sig, self._handler)
            except (ValueError, OSError):   # non-main thread / platform
                continue
        self._installed = True
        return self

    def uninstall(self) -> None:
        """Restore the previous handlers (idempotent)."""
        if not self._installed:
            return
        for sig, prev in self._prev.items():
            try:
                _signal.signal(sig, prev)
            except (ValueError, OSError):   # pragma: no cover
                continue
        self._prev.clear()
        self._installed = False

    def __enter__(self) -> "GracefulShutdown":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()


def _select_tree(flag, new, old):
    """Per-leaf ``where(flag, new, old)`` — the carry gate for masked
    (padded) steps.  ``flag`` is a traced bool scalar, so the whole tail
    window runs data-dependently with zero retraces."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(flag, n, o), new, old)


class _AotLoop:
    """One dispatch through a warmed AOT executable, with jit fallback.

    The compiled executable rejects arguments whose sharding/layout
    drifted from the warmed signature; on such a failure the stale
    entry is dropped and the dispatch retries through the jit path
    (which traces/compiles as usual), so a bad warmup can cost at most
    one compile — never a crash.  Only argument-VALIDATION errors
    (ValueError/TypeError, raised before donation takes effect, so the
    fallback re-uses the same live buffers) are treated as drift;
    genuine runtime failures (device OOM, deleted buffers) propagate —
    silently re-running them through a fresh compile would mask the
    error AND double the damage."""

    def __init__(self, pipe, key, compiled, jit_loop):
        self._pipe, self._key = pipe, key
        self._compiled, self._jit = compiled, jit_loop

    def __call__(self, state, window, valid):
        try:
            return self._compiled(state, window, valid)
        except (ValueError, TypeError):
            self._pipe._aot.pop(self._key, None)
            return self._jit(state, window, valid)


class StepPipeline:
    """K train steps per host dispatch, as one compiled device loop.

    ``step_fn(state, batch) -> (state, metrics)`` is the usual fully-jitted
    amp step (:func:`apex_tpu.training.make_train_step`).  The pipeline
    compiles it into ``lax.scan`` over a ``[K, ...]``-stacked batch window
    (:func:`apex_tpu.training.chain_steps`) so host dispatch, argument
    marshalling, and metric plumbing cost once per K steps.

    Two programs back one pipeline:

    * the **hot loop** — full windows, no masking overhead, compiled once
      per (K, shapes);
    * the **tail loop** — same signature, per-step carry select-gated on a
      ``[K]`` bool ``valid`` mask; compiled lazily the first time a ragged
      window (``n_valid < k``) shows up, then reused for every tail.

    ``donate_window=True`` (default) donates the consumed window alongside
    the state (``donate_argnums=(0, 1)``), releasing its device memory for
    the next staged window; pass ``False`` when cycling a pre-staged pool
    of windows (re-using a donated buffer is an error).

    ``wrap`` is applied to the loop function — signature
    ``(state, window, valid) -> (state, metrics)`` — before ``jax.jit``;
    use it for ``shard_map`` over a mesh (the valid mask is replicated,
    spec ``P()``; the window's leading K axis stays unsharded).
    """

    def __init__(self, step_fn: Callable, k: int, *,
                 wrap: Optional[Callable] = None,
                 donate_window: bool = True,
                 telemetry=None):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = int(k)
        self._step_fn = step_fn
        self._wrap = wrap
        donate = (0, 1) if donate_window else (0,)
        self.donate_window = donate_window
        # Telemetry (ISSUE 5): an explicit Recorder pins this pipeline to
        # it; None defers to telemetry.get_recorder() per dispatch, so a
        # recorder installed mid-run is picked up.  With no recorder the
        # dispatch path below is byte-for-byte the uninstrumented one.
        self._telemetry = telemetry
        self._steps_done = 0          # global step index for events
        self._t_last_dispatch: Optional[float] = None
        self._traces_seen = {"hot": 0, "tail": 0}
        self._sigs_seen = {"hot": set(), "tail": set()}

        chained = chain_steps(step_fn)

        def hot(state, window, valid):
            del valid                     # full window: nothing to mask
            return chained(state, window)

        def masked_step(state, xs):
            batch, valid = xs
            new_state, metrics = step_fn(state, batch)
            # Padded steps run (same program, no retrace) but their state
            # update is gated out, so the carry leaving the window is
            # exactly the carry after the last REAL step.
            return _select_tree(valid, new_state, state), metrics

        def tail(state, window, valid):
            return jax.lax.scan(masked_step, state, (window, valid))

        if wrap is not None:
            hot, tail = wrap(hot), wrap(tail)
        #: the hot-window jitted callable — one compile per (K, shape);
        #: wrap in ``prof.assert_trace_count`` to pin that.
        self.loop = jax.jit(hot, donate_argnums=donate)
        #: the ragged-tail jitted callable (compiled on first tail, ever).
        self.tail_loop = jax.jit(tail, donate_argnums=donate)
        self._full_valid = np.ones((self.k,), np.bool_)
        # AOT-warmed executables (ISSUE 7): (program, window signature)
        # -> compiled, installed by warmup(); step_window dispatches to
        # them directly, bypassing jit tracing entirely.
        self._aot: dict = {}
        # (state, window) ShapeDtypeStruct templates captured at the
        # first dispatch — memory_stats()'s relower fallback when no
        # AOT executable holds the compiled program (ISSUE 10).
        self._mem_template = None

    def warmup(self, state, window, *, tail: bool = False):
        """AOT-compile the device loop for this ``(state, window)``
        signature BEFORE step 0 (``apex_tpu.cache.warmup``:
        ``lower().compile()`` over abstract shapes — nothing runs,
        nothing is donated, ``state``/``window`` may be live arrays or
        ``ShapeDtypeStruct`` templates).  Subsequent ``step_window``
        calls with matching windows dispatch straight to the compiled
        executable: zero traces and zero compiles after step 0 (pin
        with ``prof.assert_trace_count(pipe.loop, 0)``), and the call-1
        donated-sharding re-specialization never happens because the
        jit cache is never consulted.  ``tail=True`` also pre-compiles
        the masked ragged-tail program.  With
        :func:`apex_tpu.cache.enable` the backend compiles are disk
        hits on the second process start.  Returns ``self``.
        """
        from . import cache as _cache
        sig = _cache.signature(window)
        self._aot[("hot", sig)] = _cache.warmup(
            self.loop, state, window, self._full_valid)
        if tail:
            self._aot[("tail", sig)] = _cache.warmup(
                self.tail_loop, state, window, self._full_valid)
        return self

    def compiled(self, program: str = "hot"):
        """The AOT executable :meth:`warmup` installed for ``program``
        (``"hot"``/``"tail"``), or None when it was never warmed — for
        host-side reads of the module that actually runs
        (``as_text()``, ``memory_analysis()``)."""
        for (prog, _sig), exe in self._aot.items():
            if prog == program:
                return exe
        return None

    def step_window(self, state, window, n_valid: Optional[int] = None):
        """Dispatch one window: K steps, ONE program.

        ``window`` is the batch pytree stacked on a leading K axis;
        ``n_valid`` (default K) marks a ragged tail — only the first
        ``n_valid`` steps advance the state, the padded remainder is
        select-gated out on device.  Returns ``(state, metrics)`` with
        per-step metrics stacked ``[K]`` as DEVICE arrays (no host sync;
        read them through :class:`DeferredMetrics`).
        """
        if n_valid is None or n_valid >= self.k:
            loop, valid, n, program = (self.loop, self._full_valid,
                                       self.k, "hot")
        else:
            if n_valid < 1:
                raise ValueError(f"n_valid must be >= 1, got {n_valid}")
            # [K] bool, shape-stable
            loop, valid, n, program = (self.tail_loop,
                                       np.arange(self.k) < n_valid,
                                       n_valid, "tail")
        if self._aot:
            # Warm-start fast path: a warmed (program, window-signature)
            # dispatches to the AOT executable — no tracing machinery at
            # all.  A mismatch (e.g. input sharding drift vs the warmed
            # layout) drops the stale entry and falls back to the jit
            # path, which handles anything.
            from . import cache as _cache
            key = (program, _cache.signature(window))
            aot = self._aot.get(key)
            if aot is not None:
                loop = _AotLoop(self, key, aot, loop)
        if self._mem_template is None:
            sds = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
                if hasattr(l, "shape") and hasattr(l, "dtype") else l,
                (state, window))
            self._mem_template = sds
        step0 = self._steps_done
        self._steps_done += n
        rec = (self._telemetry if self._telemetry is not None
               else _telemetry.get_recorder())
        if rec is None:
            return self._dispatch(loop, state, window, valid)
        t0 = time.perf_counter()
        gap = (0.0 if self._t_last_dispatch is None
               else t0 - self._t_last_dispatch)
        out = self._dispatch(loop, state, window, valid)
        t1 = time.perf_counter()
        self._t_last_dispatch = t1
        self._note_retrace(rec, loop, program, window, step0, dur=t1 - t0)
        # dur is the host DISPATCH time (async — the device may still be
        # running); gap is host time since the previous dispatch returned
        # (metric fetches, loader waits, python glue).
        rec.event("window", step=step0, k=self.k, n_valid=n,
                  dur=round(t1 - t0, 6), gap=round(gap, 6),
                  program=program)
        rec.metrics.histogram("window_dispatch_s").observe(t1 - t0)
        rec.metrics.histogram("window_gap_s").observe(gap)
        rec.metrics.counter("steps_dispatched").inc(n)
        # live steps/s gauge for the Prometheus exporter (ISSUE 10):
        # host-clock arithmetic on numbers already in hand — the rate
        # the host actually sustained across the last dispatch cycle.
        rec.metrics.gauge("steps_per_s").set(
            n / max(t1 - t0 + gap, 1e-9))
        return out

    def memory_stats(self, *, emit: bool = True) -> Optional[dict]:
        """Peak-HBM ledger of the compiled hot loop (ISSUE 10): the
        byte dict of :func:`apex_tpu.prof.memory.stats_from_analysis`
        (argument/output/temp/generated/peak), or None when nothing was
        dispatched yet or the jax in use exposes no
        ``memory_analysis``.

        Cost model: a :meth:`warmup`-ed pipeline already HOLDS the
        compiled executable, so this is a pure host read; without AOT
        the hot program is re-lowered from the first dispatch's
        shape templates (seconds of host work at exit time — with
        :func:`apex_tpu.cache.enable` the backend compile is a disk
        hit).  ``emit=True`` also records the ``memory`` event +
        ``peak_hbm_bytes`` gauge on the active recorder, which is what
        the examples' exit ``health:`` line and the ``memory_headroom``
        watchdog rule read."""
        from .prof import memory as _memory

        stats = None
        compiled = self.compiled("hot")
        if compiled is not None:
            try:
                stats = _memory.stats_from_analysis(
                    compiled.memory_analysis())
            except Exception:
                stats = None
        if stats is None and self._mem_template is not None:
            state_sds, window_sds = self._mem_template
            try:
                compiled = self.loop.lower(
                    state_sds, window_sds, self._full_valid).compile()
                stats = _memory.stats_from_analysis(
                    compiled.memory_analysis())
            except Exception:
                stats = None
        if stats is None:
            return None
        stats["source"] = "memory_analysis"
        if emit:
            rec = (self._telemetry if self._telemetry is not None
                   else _telemetry.get_recorder())
            if rec is not None:
                _memory.record_memory(rec, stats)
        return stats

    def _note_retrace(self, rec, loop, program: str, window,
                      step0: int, dur: float = 0.0) -> None:
        """Emit a ``retrace`` event when this dispatch grew the jit
        tracing cache, keyed by the window's shape signature (one int
        compare per dispatch; the signature is only built on growth).

        ``first`` marks the program's initial compile; ``new_sig``
        distinguishes a TRUE retrace (a window shape/dtype signature
        never traced before — the J004 bug class) from the known-benign
        call-1 re-specialization, where jit re-caches on the donated
        state's returned sharding with the SAME signature.  Only
        not-first + new-sig growth increments the ``retraces`` counter
        the analyzer and bench gate on.

        ``dur`` is the dispatch duration of the call that grew the
        cache — trace+compile time plus the enqueue, i.e. the compile
        share of the steady-vs-best-window gap.  The timeline analyzer
        sums it into ``retraces.compile_s`` and the roofline ledger's
        gap attribution reads it (ISSUE 6)."""
        try:
            size = loop._cache_size()
        except Exception:
            return
        prev = self._traces_seen.get(program, 0)
        if size <= prev:
            return
        self._traces_seen[program] = size
        leaves = jax.tree_util.tree_leaves(window)
        sig = "|".join(f"{getattr(l, 'dtype', type(l).__name__)}"
                       f"{list(getattr(l, 'shape', ()))}"
                       for l in leaves[:16])
        new_sig = sig not in self._sigs_seen[program]
        self._sigs_seen[program].add(sig)
        rec.event("retrace", program=program, step=step0,
                  n_traces=size, first=(prev == 0), new_sig=new_sig,
                  sig=sig, dur=round(dur, 6))
        if prev > 0 and new_sig:
            rec.metrics.counter("retraces").inc()

    def _dispatch(self, loop, state, window, valid):
        if not self.donate_window:
            return loop(state, window, valid)
        with warnings.catch_warnings():
            # The window rarely matches an output aval, so backends
            # without XLA buffer-donor support warn that the donation
            # was "not usable" at compile time; where the feature exists
            # (current TPU jaxlibs) the donation releases the window's
            # HBM for reuse while the loop runs.  The intent is
            # deliberate either way — keep the compile log clean.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return loop(state, window, valid)

    def run(self, state, windows: Iterable, *,
            on_metrics: Optional[Callable] = None):
        """Drive the pipeline over ``(window, n_valid)`` pairs (the
        :func:`stage_windows` protocol).  ``on_metrics``, when given, is
        called with a :class:`WindowMetrics` one dispatch behind the hot
        loop.  Returns ``(state, reader)``; ``reader.last()`` drains the
        final window's metrics."""
        reader = DeferredMetrics(telemetry=self._telemetry)
        for window, n_valid in windows:
            state, metrics = self.step_window(state, window, n_valid)
            prev = reader.push(metrics, n_valid)
            if prev is not None and on_metrics is not None:
                on_metrics(prev)
        if on_metrics is not None:
            for wm in reader.flush():   # the final in-flight window
                on_metrics(wm)
        return state, reader


class WindowMetrics(NamedTuple):
    """One window's stacked per-step metrics, still on device.

    ``step`` is the global index of the window's FIRST step; ``n_valid``
    how many leading entries are real (a ragged tail pads to K).
    ``fetch()`` is the one sanctioned host transfer — a single stacked
    device->host read of everything the window recorded."""
    step: int
    n_valid: int
    metrics: Any
    #: optional telemetry Recorder: fetch() reports the transfer to it
    #: (the piggyback point — telemetry reads ride THIS fetch, never a
    #: fetch of their own).
    telemetry: Any = None

    def fetch(self):
        """ONE batched device->host transfer of this window's metrics
        (each leaf arrives as a host array stacked ``[K]``; entries past
        ``n_valid`` are padding)."""
        if self.telemetry is None:
            return jax.device_get(self.metrics)  # jaxlint: disable=J001 -- the deferred reader's contract: one batched transfer, one dispatch behind the hot loop
        import time as _time
        t0 = _time.perf_counter()
        vals = jax.device_get(self.metrics)  # jaxlint: disable=J001 -- same sanctioned transfer as above, timed for the telemetry stream
        self.telemetry.observe_window_metrics(
            self.step, self.n_valid, vals, _time.perf_counter() - t0)
        return vals


class DeferredMetrics:
    """One-dispatch-behind metric reader.

    ``push`` stores the window just dispatched and returns the PREVIOUS
    window's :class:`WindowMetrics` — device handles only, no transfer.
    The caller fetches (``.fetch()``) at its own cadence; because the
    fetch always trails the newest dispatch by one window, the device is
    already executing window N while the host waits on window N-1's
    values, so the hot loop never drains the pipeline on a scalar.
    At loop exit, :meth:`flush` (or ``last()``) drains the final
    in-flight window — every pushed window is handed back exactly once
    between ``push`` returns and one ``flush``, so no metrics window is
    silently dropped (ISSUE 5 satellite).

    ``telemetry`` pins a Recorder whose ``observe_window_metrics`` rides
    each window's fetch; None defers to the active recorder at push
    time."""

    def __init__(self, telemetry=None):
        self._held: Optional[WindowMetrics] = None
        self._behind: Optional[WindowMetrics] = None
        self._next_step = 0
        self._telemetry = telemetry
        self._flushed = False

    def push(self, metrics, n_valid: int) -> Optional[WindowMetrics]:
        """Record a freshly dispatched window; returns the previous
        window's handles (or None on the first push)."""
        rec = (self._telemetry if self._telemetry is not None
               else _telemetry.get_recorder())
        self._behind = self._held
        self._held = WindowMetrics(self._next_step, n_valid, metrics, rec)
        self._next_step += n_valid
        self._flushed = False
        return self._behind

    def behind(self) -> Optional[WindowMetrics]:
        """The window one dispatch behind the newest (unfetched view)."""
        return self._behind

    def newest(self) -> Optional[WindowMetrics]:
        """The most recently pushed window (fetching it waits for the
        device to finish it — end-of-loop use only)."""
        return self._held

    def flush(self) -> list:
        """Drain the reader: return every window ``push`` has not yet
        handed back — exactly the newest in-flight one (each earlier
        window was returned by its successor's ``push``).  Returns
        ``[WindowMetrics]`` (handles; call ``.fetch()`` to read), or
        ``[]`` when already drained / nothing was pushed.  Call at loop
        exit so the final window's metrics are never silently dropped;
        idempotent until the next ``push``."""
        if self._held is None or self._flushed:
            return []
        self._flushed = True
        return [self._held]

    def last(self) -> Optional[Any]:
        """Fetch the NEWEST window's metrics (host values).  Blocks until
        the device finishes it — call once, after the loop.  Equivalent
        to ``flush()`` + fetch, and marks the reader drained."""
        if self._held is None:
            return None
        self._flushed = True
        return self._held.fetch()

    @property
    def steps_pushed(self) -> int:
        return self._next_step


def window_batches(batches: Iterable, k: int, *,
                   transform: Optional[Callable] = None,
                   pad_tail: bool = True) -> Iterator:
    """Group a per-step batch stream into host-stacked ``[k, ...]``
    windows; yields ``(window, n_valid)``.

    A final ragged group is padded to ``k`` by repeating its last batch
    (``n_valid`` marks the real count; :class:`StepPipeline` gates the
    padding out on device) — or dropped when ``pad_tail=False``, the
    ``drop_last`` analog.  ``transform`` runs per BATCH before stacking
    (decode/normalize), on the caller's thread — wrap the result in
    :class:`apex_tpu.data.PrefetchLoader` (or use :func:`stage_windows`)
    to move it off the hot loop.
    """
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    for group in _group_batches(batches, k, pad_tail):
        yield _assemble_window(group, k, transform)


def _assemble_window(group, k: int, transform: Optional[Callable]):
    """One window from one ``_group_batches`` group: per-batch
    ``transform``, tail pad with the TRANSFORMED last batch (padding
    before the transform would re-run the whole decode/augment ``k - n``
    extra times), host stack.  Shared by :func:`window_batches` (caller
    thread) and :func:`stage_windows` (worker pool) so the two paths
    cannot diverge."""
    items, n_valid = group
    if transform is not None:
        items = [transform(b) for b in items]
    if len(items) < k:
        items = items + [items[-1]] * (k - len(items))
    window = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *items)
    return window, n_valid


def _group_batches(batches: Iterable, k: int, pad_tail: bool) -> Iterator:
    """Group a batch stream into ``(list of <= k raw items, n_valid)``
    pairs WITHOUT transforming, padding, or stacking — cheap enough to
    sit under the :class:`~apex_tpu.data.PrefetchLoader` source lock;
    the heavy per-window assembly (and the tail pad, AFTER the
    transform, so the transform runs exactly once per source batch) is
    the worker pool's job (see :func:`stage_windows`)."""
    buf = []
    for b in batches:
        buf.append(b)
        if len(buf) == k:
            yield buf, k
            buf = []
    if buf and pad_tail:
        yield buf, len(buf)


def stage_windows(batches: Iterable, k: int, *,
                  transform: Optional[Callable] = None,
                  pad_tail: bool = True, depth: int = 2,
                  device=None, workers: int = 1):
    """Window assembly + device staging through the multi-worker
    :class:`apex_tpu.data.PrefetchLoader` input engine: ``workers``
    threads each assemble WHOLE ``[k, ...]`` windows ahead (per-batch
    ``transform`` — decode/augment/normalize — plus the host stack, in
    parallel, no per-batch barrier), and the staging thread
    ``jax.device_put``s finished windows so the host->device DMA of
    window N+1 overlaps the device loop of window N (the reference
    ``data_prefetcher``'s stream-overlap, at window granularity).
    ``device`` may be a ``Sharding`` — e.g.
    ``NamedSharding(mesh, P(None, "data"))`` to shard the per-step batch
    axis while the leading K axis stays unsharded — or a
    :class:`~apex_tpu.parallel.mesh.MeshPlan`, whose
    ``window_sharding()`` (leading K unsharded, batch over dp×fsdp) is
    used so the loader's placement can never drift from the step's.

    Returns the :class:`~apex_tpu.data.PrefetchLoader` itself — iterate
    it for ``(window, n_valid)`` pairs with ``window`` already on device
    (fresh buffers, safe to donate under
    ``StepPipeline(donate_window=True)``); read ``.stats.snapshot()``
    for the queue-depth / producer-stall / consumer-wait counters
    (``loader_stall_pct``, the number ``bench.py`` reports per example);
    and ``close()`` it (or use it as a context manager) to
    deterministically release the worker threads and any staged device
    windows when abandoning the stream early.
    """
    from .data import PrefetchLoader

    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if hasattr(device, "window_sharding"):      # a MeshPlan (ISSUE 12)
        device = device.window_sharding()
    # PrefetchLoader device_puts every leaf with a .shape — the window
    # arrays — and passes the plain-int n_valid through untouched.
    return PrefetchLoader(_group_batches(batches, k, pad_tail),
                          depth=depth, device=device,
                          transform=lambda g: _assemble_window(
                              g, k, transform),
                          workers=workers)
