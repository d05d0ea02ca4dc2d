"""Process-local observability: metrics, tracing, convergence telemetry.

This package is the measurement layer every perf-facing subsystem
reports through — the serving engine's per-route latency percentiles
and stage spans, the solver core's iterations-to-converge telemetry,
and the roofline-vs-achieved kernel report all sit on these three
primitives:

* :mod:`repro.obs.metrics` — counters, gauges and fixed-bucket
  histograms with p50/p90/p99 readout, grouped in a
  :class:`~repro.obs.metrics.MetricsRegistry` whose ``snapshot()`` is
  plain JSON-serializable.
* :mod:`repro.obs.tracing` — lightweight nested spans
  (``with tracer.span("solve", route=...)``) recording wall time and,
  via :meth:`~repro.obs.tracing.Span.fence`, ``block_until_ready``-
  fenced device time; finished root spans land in a ring buffer of the
  last N trace records, and every span is a ``fcm.<name>`` annotation
  in the JAX profiler's trace.
* a module-level default registry (:func:`default_registry`) that the
  solver core records convergence telemetry into — see
  ``repro.core.solver._record_telemetry``.

Nothing here imports from ``repro.core``/``repro.serving``/
``repro.kernels``, so any layer can depend on it without cycles.
"""
from .metrics import (ITER_EDGES, LATENCY_EDGES, UNIT_EDGES,  # noqa: F401
                      Counter, Gauge, Histogram, MetricsRegistry,
                      default_registry, json_safe, scoped_registry)
from .tracing import Span, Tracer  # noqa: F401
