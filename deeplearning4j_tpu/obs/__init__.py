"""Unified observability layer: metrics registry + trace spans.

One import surface for instrumented subsystems::

    from deeplearning4j_tpu import obs

    _STEPS = obs.counter("train.steps_total", "Parameter updates applied")
    with obs.span("fit.dispatch_group", steps=k):
        ...
    _STEPS.inc(k)

``obs.metrics`` (docs in that module) aggregates Counters/Gauges/
Histograms/Timers process-wide and exports them as JSON
(:func:`metrics_snapshot`), Prometheus text (:func:`prometheus_text`) —
both served by ``ui/server.py`` — and the compact summary ``bench.py``
embeds. ``obs.tracing`` writes spans into the profiler's own trace
(``jax.profiler.TraceAnnotation`` named ``dl4j:<name>``): one ``.xplane.pb``,
one clock, whoever started the trace.

This package records host scalars only, never takes a device array and never
syncs — see the host-sync contract in ``obs/metrics.py`` and
docs/OBSERVABILITY.md. It imports nothing of jax with the package; the one
lazy import is ``jax.profiler`` on the first span, which touches no device.
"""

from deeplearning4j_tpu.obs import metrics, tracing
from deeplearning4j_tpu.obs.metrics import (counter, gauge, histogram, timer,
                                            metrics_snapshot, metrics_summary,
                                            prometheus_text, reset_metrics)
from deeplearning4j_tpu.obs.tracing import span

__all__ = ["metrics", "tracing", "counter", "gauge", "histogram", "timer",
           "metrics_snapshot", "metrics_summary", "prometheus_text",
           "reset_metrics", "span"]
