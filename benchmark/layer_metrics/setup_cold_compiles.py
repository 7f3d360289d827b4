"""Programs compiled before the window opened for which the persistent cache
was asked and did not serve: requests - hits, from the program's compile log
(as ``setup_trace_lower_s``). 0 on a warm start, so a ``setup_s`` that moved
while this read 0 moved in the program and not in the cache; above 0, the
run's ``setup_s`` says which entries the cache still held. None where the
program keeps no such log. Layer: build."""

from benchmark.layer_metrics import setup_trace_lower_s


def read(ctx):
    before = setup_trace_lower_s.programs(ctx)
    if before is None:
        return None
    return sum(e["cache_asked"] and not e["cache_served"] for e in before)
