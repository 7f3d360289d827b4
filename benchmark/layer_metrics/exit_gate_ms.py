"""Device milliseconds a step under the scope ``exit_gate``: the gate's
``hidden -> 1`` product, the exit distribution, its entropy and the weighting
of each exit's loss, forward and backward, all exits. Layer: model step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx), {"exit_gate"})
