"""Device milliseconds a step under the scope ``logits_loss``: the tied
vocabulary-wide product, the log-softmax and the loss, forward and backward.
Layer: model step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx), {"logits_loss"})
