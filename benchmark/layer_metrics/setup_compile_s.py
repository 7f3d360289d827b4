"""Seconds of set-up inside the backend's compile-or-retrieve (JAX's
``backend_compile_duration``: XLA's and Mosaic's compile on a cold start, the
read and load of the persistent cache's entry on a warm one), summed over every
program compiled before the window opened; from the program's compile log, as
``setup_trace_lower_s``. None where the program keeps no such log.
Layer: build."""

from benchmark.layer_metrics import setup_trace_lower_s


def read(ctx):
    before = setup_trace_lower_s.programs(ctx)
    if before is None:
        return None
    return sum(e["backend_seconds"] for e in before)
