"""Unified observability layer: metrics registry + trace spans.

One import surface for instrumented subsystems::

    from deeplearning4j_tpu import obs

    _STEPS = obs.counter("train.steps_total", "Parameter updates applied")
    with obs.span("fit.dispatch_group", steps=k):
        ...
    _STEPS.inc(k)

``obs.metrics`` (docs in that module) aggregates Counters/Gauges/
Histograms/Timers process-wide and exports them as JSON
(:func:`metrics_snapshot`) and Prometheus text (:func:`prometheus_text`),
both served by ``ui/server.py``. ``obs.tracing`` writes spans into the
profiler's own trace (``jax.profiler.TraceAnnotation`` named ``dl4j:<name>``):
one ``.xplane.pb``, one clock, whoever started the trace. ``obs.compilation``
hears JAX's trace, lowering, compile and cache events through the process's
one ``jax.monitoring`` registration: the ``compile.*`` counters, the log
:func:`compiles` with one entry a compiled program, and :func:`building`, the
bracket by which the program says whose compile it was.

This package records host scalars only, never takes a device array and never
syncs — see the host-sync contract in ``obs/metrics.py`` and
docs/OBSERVABILITY.md. It imports nothing of jax with the package; the lazy
imports are ``jax.profiler`` on the first span and ``jax.monitoring`` on
``compilation.install()``, which touch no device.
"""

from deeplearning4j_tpu.obs import compilation, metrics, tracing
from deeplearning4j_tpu.obs.compilation import building, compiles
from deeplearning4j_tpu.obs.metrics import (counter, gauge, histogram, timer,
                                            metrics_snapshot,
                                            prometheus_text, reset_metrics)
from deeplearning4j_tpu.obs.tracing import span

__all__ = ["compilation", "metrics", "tracing", "counter", "gauge",
           "histogram", "timer", "metrics_snapshot", "prometheus_text",
           "reset_metrics", "span", "building", "compiles"]
