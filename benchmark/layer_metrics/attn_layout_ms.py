"""Device milliseconds a step under the scope ``attn`` that is not a Pallas
kernel: the head-major copies and transposes around the three flash kernels
and the lane-replicated ``lse`` / ``delta`` broadcasts, forward and backward.
Layer: kernels."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx), {"attn"},
                                  kernels=False)
