"""Host-side spans, written into the profiler's own trace.

``with span("fit.dispatch_group"):`` is a ``jax.profiler.TraceAnnotation``
named ``dl4j:fit.dispatch_group``. Whenever anyone traces the process
(``ProfilerListener``, ``jax.profiler.trace``, the benchmark's ``--trace 1``)
the host plane of the SAME ``.xplane.pb`` that holds the device ops then holds
the program's spans, on the line of the thread that ran them and on the device
events' clock: prefetch-worker and trainer spans interleave with the XLA ops
they caused, and an idle gap of the device can be laid to what the host was
doing in it (``python3 -m benchmark.scope_reduce <file.xplane.pb>``,
docs/OBSERVABILITY.md).

There is no switch and no file of its own: outside a profiler session an
annotation is one small object and a flag test, and records nothing.

Like ``obs.metrics``, a span takes host scalars only, never a device array,
and never syncs (the G001 carve-out contract, docs/STATIC_ANALYSIS.md).
``jax.profiler`` is imported on the first span, not with the package: a
process that only reads metrics never loads it.
"""

from __future__ import annotations

__all__ = ["span", "PREFIX"]

PREFIX = "dl4j:"

_annotation = None


def span(name, **args):
    """Context manager marking its body as one span of the calling thread in
    the profiler's trace. ``args`` become the event's arguments — keep them
    small host values."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation(PREFIX + name, **args)
