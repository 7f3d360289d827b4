"""Seconds of set-up spent TRACING Python functions to jaxprs and LOWERING the
jaxprs to modules, summed over every program the process compiled before the
window opened: the program's compile log (``deeplearning4j_tpu.obs.compiles()``,
one entry a program, fed by ``jax.monitoring``). Python's share of ``setup_s``:
no cache saves it, it is paid on every start, and it grows with unrolled depth
and with each Pallas call site. The log starts with the first model object, so
the benchmark's imports and backend start are in ``setup_s`` and not here.
None where the program keeps no such log. Layer: build."""


def programs(ctx):
    """The log's entries that closed before the window opened (on
    ``time.perf_counter()``, the clock of ``Spans.window_start``)."""
    from deeplearning4j_tpu import obs
    log = getattr(obs, "compiles", None)
    if log is None:
        return None
    start = ctx["spans"].window_start
    return [e for e in log() if e["end"] < start]


def read(ctx):
    before = programs(ctx)
    if before is None:
        return None
    return sum(e["trace_seconds"] + e["lower_seconds"] for e in before)
