"""Device milliseconds a step in the instructions under the scope
``optimizer`` (AdamW, the EMA): whatever XLA rooted there, which can include a
weight-gradient product fused into the update (PERF.md section 5 says which).
Layer: model step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx), {"optimizer"})
