"""Production telemetry: metrics registry + request-lifecycle tracing.

``paddle_tpu.obs`` is the observability layer the serving engine
(serving/metrics.py wires it in) and the hapi training loop record
into:

  * :class:`MetricsRegistry` — counters, gauges, log-bucketed
    :class:`Histogram` instruments with p50/p90/p99 quantile estimation,
    windowed rates, a JSON ``snapshot()`` and Prometheus text
    exposition (``prometheus()``);
  * :class:`Tracer` — ring-buffered per-request lifecycle :class:`Span`
    records and discrete events (compiles, evictions, head-of-line
    skips, slot churn), exportable as Chrome-trace request lanes that
    merge into ``profiler.export_chrome_tracing`` output.

Everything here is pure host code: no jax import, no device arrays, no
added syncs — the hard constraint tests/test_observability.py pins.
See docs/observability.md for the glossary, span model and export
formats.
"""

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .tracing import Span, Tracer

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "Span", "Tracer"]
