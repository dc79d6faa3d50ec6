"""``repro.obs`` — observability substrate for the serve/train runtimes.

Three pieces (see ``src/repro/obs/README.md`` for the full taxonomy and
schema docs):

* :class:`Tracer` (``trace.py``) — structured lifecycle events (request
  spans, per-dispatch device spans, speculation/resize/preemption/...
  instants) on a bounded counted-drops ring buffer, with a zero-overhead
  disabled mode (:data:`NULL_TRACER`);
* :class:`MetricsRegistry` (``metrics.py``) — counters / gauges /
  fixed-size-reservoir histograms with stable dotted names and a versioned
  snapshot schema; the single source of truth behind ``stats()``;
* :func:`fit_offset` (``clock.py``) — the tracer's clock fitted onto a
  ``jax.profiler`` capture's, from the ``t_ns`` argument of phase spans;
* exporters + CLI (``export.py`` / ``check.py`` / ``__main__.py``) —
  Chrome trace-event JSON that opens in ui.perfetto.dev, and
  ``python -m repro.obs summarize|diff|check`` over the artifacts.
"""
from repro.obs.clock import ClockFit, fit_offset, profiler_phases
from repro.obs.export import (TRACE_SCHEMA, TRACE_VERSION, chrome_trace,
                              load_trace, write_chrome_trace)
from repro.obs.metrics import (METRICS_SCHEMA, METRICS_VERSION, Counter,
                               Gauge, Histogram, MetricsRegistry,
                               load_snapshot, metric_scalar)
from repro.obs.render import format_stats
from repro.obs.trace import (NULL_TRACER, Event, Tracer, is_instrumentation,
                             mark_instrumentation)

__all__ = [
    "ClockFit", "Counter", "Event", "Gauge", "Histogram", "MetricsRegistry",
    "METRICS_SCHEMA", "METRICS_VERSION", "NULL_TRACER", "TRACE_SCHEMA",
    "TRACE_VERSION", "Tracer", "chrome_trace", "fit_offset", "format_stats",
    "is_instrumentation", "load_snapshot", "load_trace",
    "mark_instrumentation", "metric_scalar", "profiler_phases",
    "write_chrome_trace",
]
