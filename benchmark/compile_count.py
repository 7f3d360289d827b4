"""How many XLA backend compiles a region triggered, from ``jax.monitoring``'s
``/jax/core/compile/backend_compile_duration`` events (jit cache hits emit
nothing; a program served from the persistent cache still emits one, so this
counts compile-or-retrieve). Copy of ``tools/compile_counter.py``'s counter,
kept here so that no later PR can change what ``compiles_in_window`` counts.
"""

from __future__ import annotations

import threading

_EVENT = "/jax/core/compile/backend_compile_duration"
_lock = threading.Lock()
_active = []
_registered = False


def _listener(event, duration, **kwargs):  # noqa: ARG001 -- monitoring API
    if event == _EVENT:
        with _lock:
            for c in _active:
                c.count += 1
                c.seconds += duration


class CompileCounter:
    """Context manager: ``count`` compiles and their wall ``seconds``."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __enter__(self):
        global _registered
        with _lock:
            if not _registered:
                import jax.monitoring
                jax.monitoring.register_event_duration_secs_listener(_listener)
                _registered = True
            _active.append(self)
        return self

    def __exit__(self, *exc):
        with _lock:
            _active.remove(self)
        return False
