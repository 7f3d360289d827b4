"""Device milliseconds a step under the scopes ``attn_norm`` and ``mlp_norm``:
the RMSNorm on each sublayer's OUTPUT, before the residual sum, forward and
backward, every layer application. Unlike the pre-norms (``layernorm_ms``)
they follow a product and cannot ride in the next one's prologue. Layer: model
step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx),
                                  {"attn_norm", "mlp_norm"})
