"""XLA compilation counters: how many backend compiles, and how many
persistent-cache hits and writes, a code region triggered.

The recompile regressions this repo fights (one fresh ``_jit_train`` entry per
trailing-batch shape — the exact overhead the fused loop's shape bucketing
removes) are invisible in wall-time assertions on fast hosts. These counters
make them a hard number tests and ``chip_smoke.py`` can gate on.

Both are before / after differences of the tallies that the process's one
``jax.monitoring`` registration keeps (``deeplearning4j_tpu/obs/compilation.py``:
one per ``/jax/core/compile/backend_compile_duration`` event — jit cache hits
emit nothing — and the cache's events), so nested counters, and counters on
any thread, each see every event, whatever ``DL4J_TPU_METRICS`` says.

Usage::

    from tools.compile_counter import CompileCounter

    with CompileCounter() as cc:
        net.fit(iterator)
    assert cc.count <= expected
"""

from __future__ import annotations

from deeplearning4j_tpu.obs import compilation


class _Difference:
    """Context manager over the tallies' growth since entry: live inside the
    body, frozen on leaving."""

    def __init__(self):
        self._start = self._end = compilation.tallies()

    def __enter__(self):
        compilation.install()
        self._start, self._end = compilation.tallies(), None
        return self

    def __exit__(self, *exc):
        self._end = compilation.tallies()
        return False

    def _grown(self, tally):
        end = self._end or compilation.tallies()
        return end[tally] - self._start[tally]


class CompileCounter(_Difference):
    """Context manager counting XLA backend compilations in its body
    (``count``) and the wall seconds they took (``seconds``)."""

    @property
    def count(self):
        return self._grown("programs")

    @property
    def seconds(self):
        return self._grown("backend_seconds")


class CompileCacheCounter(_Difference):
    """Counts persistent-XLA-cache (``JAX_COMPILATION_CACHE_DIR``, else
    ``<repo>/.jax_cache``) hits and misses in its body. ``misses == 0 and hits > 0`` is THE
    "warm restart compiles nothing" assertion for server warm-start:
    current jax versions emit ``backend_compile_duration`` even when the
    executable is served from the persistent cache (the event times the
    compile-OR-retrieve path), so :class:`CompileCounter` alone cannot
    distinguish a cache-served boot from a cold one. ``misses`` is jax's
    ``cache_misses`` event, which fires where an entry is WRITTEN
    (``compile.cache_writes_total``)."""

    @property
    def hits(self):
        return self._grown("cache_hits")

    @property
    def misses(self):
        return self._grown("cache_writes")
