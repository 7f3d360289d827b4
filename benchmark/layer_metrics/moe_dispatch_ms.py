"""Device milliseconds a step under the scopes ``router`` (logits, scores, the
top k) and ``moe_dispatch`` (the sort by expert, the gather into the row
buffer, the weighted scatter back to the tokens), forward and backward, all
sparse layers: what the expert layer costs outside its products. Layer: model
step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx),
                                  {"router", "moe_dispatch"})
