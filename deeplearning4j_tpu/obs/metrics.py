"""Typed, thread-safe metric registry: the ONE place step durations, queue
depths, collective round latencies, and checkpoint commit times live.

The reference ships a full stats pipeline (StatsListener → storage →
training UI, SURVEY §5.1); this module is its process-wide aggregation
core for the TPU-first repro. Every subsystem records into named metrics
here and two export surfaces read them back out:

- :func:`metrics_snapshot` — the full registry as a JSON-able dict, with
  the log of compiled programs (``obs/compilation.py``) under ``compiles``
  (served at ``/train/metrics/data`` by ``ui/server.py``);
- :func:`prometheus_text` — Prometheus text exposition (``/metrics``).

Metric kinds: :class:`Counter` (monotonic), :class:`Gauge` (last value),
:class:`Histogram` (fixed bucket bounds, cumulative at export, with a
``time()`` context-manager Timer reading the monotonic clock). Names are
dotted (``train.dispatch_group_seconds``); the catalogue lives in
docs/OBSERVABILITY.md.

Host-sync discipline (the same contract as the NaN guard): recording
helpers accept HOST scalars only — python numbers, or device scalars a
caller has ALREADY synced at a dispatch-group boundary. Nothing in this
module touches jax, so a record can never force a device→host sync; a
caller handing a live device array to ``record()`` is performing the sync
itself and owns that decision (graftlint G001 exempts this module on that
contract — see docs/STATIC_ANALYSIS.md).

``DL4J_TPU_METRICS=0`` turns every record into an early-out (one env read
+ branch — near-zero overhead); the knob is read at CALL time per the
registry contract, so tests and tools may flip it after import. Metric
objects are always registered, so a disabled run still exports a complete
(all-zero) catalogue.
"""

from __future__ import annotations

import threading
import time

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
           "timer", "enabled", "value", "metrics_snapshot",
           "prometheus_text", "reset_metrics", "TIME_BUCKETS"]

# default bucket bounds (seconds) for duration histograms: half-millisecond
# dispatch latencies up through minute-scale collective deadlines
TIME_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

_REGISTRY = {}          # name -> metric, insertion-ordered
_REGISTRY_LOCK = threading.Lock()


def enabled():
    """Whether recording is on (``DL4J_TPU_METRICS``, default on). Read at
    call time; a disabled registry still registers and exports metrics —
    their values simply stay zero."""
    from deeplearning4j_tpu.config import env_flag
    return env_flag("DL4J_TPU_METRICS")


class _Metric:
    kind = "metric"

    def __init__(self, name, doc):
        self.name = name
        self.doc = doc
        self._lock = threading.Lock()

    def reset(self):
        raise NotImplementedError

    def snapshot(self):
        raise NotImplementedError


class Counter(_Metric):
    """Monotonically increasing count (events, steps, bytes)."""

    kind = "counter"

    def __init__(self, name, doc):
        super().__init__(name, doc)
        self._value = 0

    def inc(self, n=1):
        if not enabled():
            return
        with self._lock:
            self._value += n

    @property
    def value(self):
        with self._lock:   # pair with inc() under the writers' lock
            return self._value

    def reset(self):
        with self._lock:
            self._value = 0

    def snapshot(self):
        with self._lock:
            return self._value


class Gauge(_Metric):
    """Last observed value (queue depth, world size)."""

    kind = "gauge"

    def __init__(self, name, doc):
        super().__init__(name, doc)
        self._value = 0

    def set(self, v):
        if not enabled():
            return
        # single assignment: GIL-atomic, no lock needed for a last-writer-
        # wins gauge (the prefetch worker sets queue depth per item)
        self._value = v   # graftlint: disable=G015 -- deliberate lock-free last-writer-wins gauge: the assignment is GIL-atomic, a reader (exporter/heartbeat thread) seeing the previous value is by definition correct for a gauge

    @property
    def value(self):
        return self._value

    def reset(self):
        self._value = 0

    def snapshot(self):
        return self._value


class Histogram(_Metric):
    """Fixed-bound bucket histogram with count/sum/min/max, plus a
    ``time()`` context-manager Timer over the monotonic clock. Bounds are
    upper edges; one overflow bucket (+Inf) is implicit."""

    kind = "histogram"

    def __init__(self, name, doc, buckets=TIME_BUCKETS):
        super().__init__(name, doc)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._counts = [0] * (len(self.buckets) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None

    def record(self, v):
        """Record one HOST scalar observation (see the module contract)."""
        if not enabled():
            return
        v = float(v)
        i = 0
        for b in self.buckets:
            if v <= b:
                break
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    def time(self):
        """Context manager recording the wall-clock (monotonic) duration
        of its body into this histogram — the Timer form."""
        return _Timer(self)

    @property
    def count(self):
        with self._lock:   # recorders write under the same lock
            return self._count

    @property
    def sum(self):
        with self._lock:
            return self._sum

    def quantile(self, q):
        """Bucket-interpolated quantile estimate in [0, 1] (Prometheus
        ``histogram_quantile`` style); None when empty. The overflow
        bucket reports the observed max (no upper bound to lerp to)."""
        with self._lock:
            total = self._count
            if not total:
                return None
            rank = q * total
            seen = 0
            for i, c in enumerate(self._counts):
                if not c:
                    continue
                if seen + c >= rank:
                    if i >= len(self.buckets):
                        return self._max
                    lo = self.buckets[i - 1] if i else 0.0
                    hi = self.buckets[i]
                    frac = (rank - seen) / c
                    # clamp: bucket lerp must not report beyond observation
                    return min(lo + (hi - lo) * frac, self._max)
                seen += c
            return self._max

    def reset(self):
        with self._lock:
            self._counts = [0] * (len(self.buckets) + 1)
            self._count = 0
            self._sum = 0.0
            self._min = None
            self._max = None

    def snapshot(self):
        with self._lock:
            return {"count": self._count, "sum": self._sum,
                    "min": self._min, "max": self._max,
                    "buckets": [[b, c] for b, c in
                                zip(self.buckets + ("+Inf",), self._counts)]}


class _Timer:
    __slots__ = ("_hist", "_t0")

    def __init__(self, hist):
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._hist.record(time.perf_counter() - self._t0)
        return False


def _get_or_create(cls, name, doc, **kw):
    with _REGISTRY_LOCK:
        m = _REGISTRY.get(name)
        if m is None:
            m = cls(name, doc, **kw)
            _REGISTRY[name] = m
        elif not isinstance(m, cls):
            raise ValueError(
                f"metric {name!r} is already registered as a {m.kind}, "
                f"not a {cls.kind}")
        return m


def counter(name, doc=""):
    """Get-or-create the named :class:`Counter`."""
    return _get_or_create(Counter, name, doc)


def gauge(name, doc=""):
    """Get-or-create the named :class:`Gauge`."""
    return _get_or_create(Gauge, name, doc)


def histogram(name, doc="", buckets=TIME_BUCKETS):
    """Get-or-create the named :class:`Histogram` (bounds fixed at first
    creation)."""
    return _get_or_create(Histogram, name, doc, buckets=buckets)


def timer(name, doc=""):
    """Context manager timing its body into histogram ``name``."""
    return histogram(name, doc).time()


def value(name):
    """Current value of a registered metric: number for counter/gauge,
    observation count for a histogram; KeyError for an unknown name."""
    m = _REGISTRY[name]
    return m.count if isinstance(m, Histogram) else m.value


def reset_metrics():
    """Zero every registered metric (registrations stay). Test/bench
    boundary helper — production metrics are cumulative, Prometheus
    style."""
    with _REGISTRY_LOCK:
        metrics = list(_REGISTRY.values())
    for m in metrics:
        m.reset()


def metrics_snapshot():
    """The whole registry as one JSON-able dict, grouped by kind, and under
    ``compiles`` the log of compiled programs (which, when, how long, whether
    the persistent cache held it: ``obs/compilation.py``)."""
    from deeplearning4j_tpu.obs import compilation
    with _REGISTRY_LOCK:
        metrics = list(_REGISTRY.values())
    out = {"enabled": enabled(),
           "counters": {}, "gauges": {}, "histograms": {}}
    for m in metrics:
        out[m.kind + "s"][m.name] = m.snapshot()
    out["compiles"] = compilation.compiles()
    return out


def _prom_name(name):
    return "dl4j_tpu_" + name.replace(".", "_").replace("-", "_")


def prometheus_text():
    """Prometheus text exposition (version 0.0.4) of the registry —
    the body of the UI server's ``/metrics`` endpoint."""
    with _REGISTRY_LOCK:
        metrics = list(_REGISTRY.values())
    lines = []
    for m in metrics:
        pname = _prom_name(m.name)
        if m.doc:
            lines.append(f"# HELP {pname} {m.doc}")
        lines.append(f"# TYPE {pname} {m.kind}")
        if isinstance(m, Histogram):
            snap = m.snapshot()
            cum = 0
            for b, c in snap["buckets"]:
                cum += c
                le = "+Inf" if b == "+Inf" else repr(float(b))
                lines.append(f'{pname}_bucket{{le="{le}"}} {cum}')
            lines.append(f"{pname}_sum {snap['sum']}")
            lines.append(f"{pname}_count {snap['count']}")
        else:
            lines.append(f"{pname} {m.snapshot()}")
    return "\n".join(lines) + "\n"
