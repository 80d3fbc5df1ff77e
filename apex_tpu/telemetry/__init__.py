"""apex_tpu.telemetry — run-wide observability engine (ISSUE 5).

The runtime counterpart of the ``prof`` package's static analysis (the
PyProf pillar, SURVEY.md §2.9): a low-overhead structured event stream
you can tail in production and analyze offline, plus a metrics registry
whose device-side values piggyback on the existing one-dispatch-behind
metric reads (zero extra host syncs per window).

* :class:`Recorder` / :func:`start` — thread-safe JSONL event stream
  (step windows, dispatch gaps, loader stage/stall, loss-scale
  skip/growth, retraces, per-psum collective bytes, and set-up: every
  lowering, backend compile and compile-cache hit, the stages of an
  AOT warm-up, the process's age at the stream's start).
* :class:`MetricsRegistry` — counters / gauges / reservoir-percentile
  histograms; a strict no-op when disabled.
* :class:`Watchdog` (:mod:`~apex_tpu.telemetry.watchdog`) — run-health
  rule engine folding events online into debounced ``alert`` events
  (non-finite loss, loss-scale collapse, loader-stall spikes, step-time
  anomalies, retrace storms); ``telemetry.start(path, watchdog=True)``.
* :func:`to_chrome_trace` — Chrome ``trace_event`` export (Perfetto).
* Offline analysis: ``python -m apex_tpu.prof.timeline run.jsonl``;
  cross-run regression diffing: ``python -m apex_tpu.prof.regress``.

Instrumented subsystems discover the active recorder through
:func:`get_recorder`; with none installed the hot paths reduce to one
global read and ``jax.monitoring`` holds no listener of ours — the
disabled path compiles the same program as an uninstrumented build
(``tests/test_setup_telemetry.py`` holds both).

See ``docs/telemetry.md`` for the event schema and overhead model.
"""

from .events import (Recorder, get_recorder, set_recorder,  # noqa: F401
                     start, start_from_env, to_chrome_trace,
                     expand_stream_paths)
from .export import PrometheusExporter, attach_exporter     # noqa: F401
from .metrics import (Counter, Gauge, Histogram,            # noqa: F401
                      MetricsRegistry, Rolling)
from .slo import SLOEngine, SLOSpec, parse_slo              # noqa: F401
from .tracing import Tracer                                 # noqa: F401
from .watchdog import Watchdog                              # noqa: F401

__all__ = ["Recorder", "get_recorder", "set_recorder", "start",
           "start_from_env", "to_chrome_trace", "expand_stream_paths",
           "PrometheusExporter", "attach_exporter", "Counter", "Gauge",
           "Histogram", "MetricsRegistry", "Rolling", "Watchdog",
           "Tracer", "SLOEngine", "SLOSpec", "parse_slo"]
