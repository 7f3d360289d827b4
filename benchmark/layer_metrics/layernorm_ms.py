"""Device milliseconds a step under the scopes ``ln1``, ``ln2`` and
``final_ln``, forward and backward. Layer: model step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx),
                                  {"ln1", "ln2", "final_ln"})
