"""What the process compiled: one ``jax.monitoring`` registration, a bounded
log with one entry a compiled program, and the ``compile.*`` counters.

Set-up is most of a short run and all of a server's boot, and from outside it
is one number. JAX says what it is made of, program by program, through
``jax.monitoring``: how long the Python function took to TRACE, how long the
jaxpr took to LOWER to a module (neither is saved by any cache: both are paid
on every start), whether the persistent cache was asked and held the program,
and how long the backend took to COMPILE OR RETRIEVE it. :func:`install`
registers the process's one pair of listeners (a duration listener and an
event listener; old JAX has no unregister, so they stay for the life of the
process) and is called by the constructors of ``TransformerLM``,
``MultiLayerNetwork``, ``ComputationGraph`` and the serving front ends:
**compiles before the first model object exists are invisible**.

The events of one program arrive in order on the thread that compiles it
(trace, lower, the cache's, backend), so an open entry per thread, closed by
the backend event, is enough. Jitted functions called inside a traced function
fire trace events of their own, and so do those a lowering rule calls (the
Pallas interpreter's, a ``lower_fun``'s): their time lies INSIDE the enclosing
event's, so the entry keeps the outermost spans only, told by their start (the
event's end less its duration, on ``time.time()`` as JAX measures). A function that is
traced and never compiled (``jax.eval_shape``, an error) leaves its trace
seconds to the next program of that thread, which is the same function where
it is then called; one that is lowered and never compiled (``.lower()`` alone)
is dropped when the next program starts.

``fun_name`` is JAX's name for the module (``jit(step)``, ``jit(<lambda>)``).
Renaming a jitted function changes the lowered text that tests hold, so the
program says whose compile it was by bracketing what it owns::

    with obs.building("lm.step"):      # thread-local, nests
        self._step = self._build_step()
        ...first call...

Every entry closed inside carries that ``owner``; the bracket is a
``dl4j:lm.step`` span in a profiler trace and sets the gauge
``lm.step.build_seconds`` to its wall time on leaving.

The tallies here count whatever ``DL4J_TPU_METRICS`` says (a compile is rare,
and ``tools/compile_counter.py`` reads them); their mirrors in ``obs.metrics``
early-out with the knob like every record. ``cache_writes`` is JAX's
``cache_misses`` event, named for what JAX does where it fires: it writes an
entry (``jax/_src/compilation_cache.py``). ``requests - hits`` programs were
compiled; ``requests - hits - writes`` were compiled and left no entry.

Host scalars only, nothing of jax but ``jax.monitoring`` on :func:`install`,
never a sync: the contract of ``obs/metrics.py``.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from deeplearning4j_tpu.obs import metrics
from deeplearning4j_tpu.obs.tracing import span

__all__ = ["install", "building", "compiles", "tallies", "subscribe"]

LOG_LENGTH = 256    # programs kept; a serving boot compiles some dozens

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
# the cache's plain events -> the entry's flag and the tally they bump
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        ("cache_asked", "cache_requests"),
    "/jax/compilation_cache/cache_hits": ("cache_served", "cache_hits"),
    "/jax/compilation_cache/cache_misses": ("cache_written", "cache_writes"),
}

_COUNTERS = {
    "programs": ("compile.programs_total",
                 "Programs compiled or retrieved from the persistent cache"),
    "trace_seconds": ("compile.trace_seconds_total",
                      "Seconds tracing Python functions to jaxprs"),
    "lower_seconds": ("compile.lower_seconds_total",
                      "Seconds lowering jaxprs to modules"),
    "backend_seconds": ("compile.backend_seconds_total",
                        "Seconds in the backend's compile-or-retrieve"),
    "cache_requests": ("compile.cache_requests_total",
                       "Programs for which the persistent cache was asked"),
    "cache_hits": ("compile.cache_hits_total",
                   "Programs the persistent cache served"),
    "cache_writes": ("compile.cache_writes_total",
                     "Entries written to the persistent cache"),
    "cache_retrieval_seconds": ("compile.cache_retrieval_seconds_total",
                                "Seconds reading and loading cache entries"),
    "cache_saved_seconds": ("compile.cache_saved_seconds_total",
                            "Compile seconds the persistent cache saved"),
}
_MIRRORS = {k: metrics.counter(*v) for k, v in _COUNTERS.items()}

_lock = threading.Lock()
_installed = False
_tallies = dict.fromkeys(_COUNTERS, 0)
_log = collections.deque(maxlen=LOG_LENGTH)
_subscribers = []
_local = threading.local()     # .entry: the open program; .owners: brackets


def install():
    """Register the process's one pair of ``jax.monitoring`` listeners.
    Idempotent; imports ``jax.monitoring`` here and not with the package."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def _count(**seen):
    with _lock:
        for name, n in seen.items():
            _tallies[name] += n
    for name, n in seen.items():
        _MIRRORS[name].inc(n)


def _open_entry():
    entry = getattr(_local, "entry", None)
    if entry is None:
        entry = _local.entry = {
            "spans": [], "cache_asked": False, "cache_served": False,
            "cache_written": False, "retrieval_seconds": 0.0}
    return entry


def _on_event(event, **kwargs):  # noqa: ARG001 -- monitoring API
    found = _CACHE_EVENTS.get(event)
    if found is not None:
        flag, tally = found
        _open_entry()[flag] = True
        _count(**{tally: 1})


def _on_duration(event, duration, **kwargs):
    if event in (_TRACE, _LOWER):
        spans = _open_entry()["spans"]
        if event == _TRACE and spans and spans[-1][1] == _LOWER:
            # lowered and never compiled: not a program, and not this one
            _local.entry = None
            spans = _open_entry()["spans"]
        start = time.time() - duration
        while spans and spans[-1][0] >= start:
            spans.pop()            # ran inside this one: its time is in it
        spans.append((start, event, duration))
    elif event == _RETRIEVAL:
        _open_entry()["retrieval_seconds"] += duration
        _count(cache_retrieval_seconds=duration)
    elif event == _SAVED:
        # negative where reading an entry took longer than compiling had
        _count(cache_saved_seconds=max(0.0, duration))
    elif event == _BACKEND:
        _close(kwargs.get("fun_name"), duration)


def _close(fun_name, backend_seconds):
    opened = _open_entry()
    _local.entry = None
    spans = opened.pop("spans")
    owners = getattr(_local, "owners", None)
    entry = {"fun_name": fun_name, "owner": owners[-1] if owners else None,
             "trace_seconds": sum(s for _, k, s in spans if k == _TRACE),
             "lower_seconds": sum(s for _, k, s in spans if k == _LOWER),
             "backend_seconds": backend_seconds, **opened,
             "end": time.perf_counter()}
    with _lock:
        _log.append(entry)
        subscribers = list(_subscribers)
    _count(programs=1, trace_seconds=entry["trace_seconds"],
           lower_seconds=entry["lower_seconds"],
           backend_seconds=backend_seconds)
    for callback in subscribers:
        callback(entry)


@contextlib.contextmanager
def building(owner):
    """Bracket what the program builds and calls for the first time: every
    program compiled by this thread inside is logged with ``owner``, the body
    is the span ``dl4j:<owner>`` and the gauge ``<owner>.build_seconds`` is
    set to its wall seconds on leaving. Brackets nest; the innermost owns."""
    owners = getattr(_local, "owners", None)
    if owners is None:
        owners = _local.owners = []
    owners.append(owner)
    t0 = time.perf_counter()
    try:
        with span(owner):
            yield
    finally:
        owners.pop()
        metrics.gauge(owner + ".build_seconds",
                      "Wall seconds of the last build bracketed by this owner"
                      ).set(time.perf_counter() - t0)


def compiles():
    """The log, oldest first, as JSON-able dicts: ``fun_name``, ``owner``
    (None outside any :func:`building`), ``trace_seconds``, ``lower_seconds``,
    ``backend_seconds`` (compile or retrieve), ``cache_asked``,
    ``cache_served``, ``cache_written``, ``retrieval_seconds`` and ``end``,
    the entry's close on ``time.perf_counter()``. The last ``LOG_LENGTH``
    programs; ``compile.programs_total`` says how many there were."""
    with _lock:
        return [dict(e) for e in _log]


def tallies():
    """The listener's own totals since :func:`install`, by the
    ``compile.*_total`` counters' middle names; never reset, whatever
    ``DL4J_TPU_METRICS`` says."""
    with _lock:
        return dict(_tallies)


def subscribe(callback):
    """Call ``callback(entry)`` for every program from now on, synchronously
    on the thread that compiled it, as its backend event ends (the stack
    still holds the call that caused the compile). Idempotent per callback;
    installs the listeners."""
    install()
    with _lock:
        if callback not in _subscribers:
            _subscribers.append(callback)
