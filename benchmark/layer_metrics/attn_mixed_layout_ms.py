"""Device milliseconds a step under the scopes ``attn_full`` and ``attn_window``
that are not a Pallas kernel: what a model with a per-layer list spends around
its flash kernels, forward, forward again under remat, and backward: the
head-major copies and transposes, the rotary embedding, the lane-replicated
``lse`` / ``delta`` broadcasts. ``attn_layout_ms`` reads the same of the scope
``attn``, which such a model does not enter. Layer: kernels."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx),
                                  {"attn_full", "attn_window"}, kernels=False)
