"""XLA compilation counter: how many backend compiles a code region triggered.

The recompile regressions this repo fights (one fresh ``_jit_train`` entry per
trailing-batch shape — the exact overhead the fused loop's shape bucketing
removes) are invisible in wall-time assertions on fast hosts. This counter
makes them a hard number tests and ``bench.py`` can gate on.

Counts ``/jax/core/compile/backend_compile_duration`` events from
``jax.monitoring`` — one per actual XLA ``backend_compile`` (jit cache hits
emit nothing). The listener is registered once per process and toggled by the
context manager, so nested counters each see every event.

Usage::

    from tools.compile_counter import CompileCounter

    with CompileCounter() as cc:
        net.fit(iterator)
    assert cc.count <= expected
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_active = []   # stack of running counters; listener is a process singleton
_registered = False

_EVENT = "/jax/core/compile/backend_compile_duration"


def _listener(event, duration, **kwargs):  # noqa: ARG001 — monitoring API
    if event == _EVENT:
        with _lock:
            for c in _active:
                c.count += 1
                c.seconds += duration


def _ensure_registered():
    global _registered
    if _registered:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_listener)
    _registered = True


class CompileCounter:
    """Context manager counting XLA backend compilations in its body
    (``count``) and the wall seconds they took (``seconds``)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __enter__(self):
        _ensure_registered()
        with _lock:
            self.count = 0
            self.seconds = 0.0
            _active.append(self)
        return self

    def __exit__(self, *exc):
        with _lock:
            _active.remove(self)
        return False


# ---------------------------------------------------------------------------
# persistent-compile-cache hit/miss counter (the warm-restart assertion)
# ---------------------------------------------------------------------------

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_cache_active = []       # stack of running CompileCacheCounters
_cache_registered = False


def _cache_listener(event, **kwargs):  # noqa: ARG001 — monitoring API
    if event in (_HIT_EVENT, _MISS_EVENT):
        with _lock:
            for c in _cache_active:
                if event == _HIT_EVENT:
                    c.hits += 1
                else:
                    c.misses += 1


def _ensure_cache_registered():
    global _cache_registered
    if _cache_registered:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_cache_listener)
    _cache_registered = True


class CompileCacheCounter:
    """Counts persistent-XLA-cache (``JAX_COMPILATION_CACHE_DIR``, else
    ``<repo>/.jax_cache``) hits and misses in its body. ``misses == 0 and hits > 0`` is THE
    "warm restart compiles nothing" assertion for server warm-start:
    current jax versions emit ``backend_compile_duration`` even when the
    executable is served from the persistent cache (the event times the
    compile-OR-retrieve path), so :class:`CompileCounter` alone cannot
    distinguish a cache-served boot from a cold one."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __enter__(self):
        _ensure_cache_registered()
        with _lock:
            self.hits = 0
            self.misses = 0
            _cache_active.append(self)
        return self

    def __exit__(self, *exc):
        with _lock:
            _cache_active.remove(self)
        return False
