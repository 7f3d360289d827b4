"""Device milliseconds a step under the scopes ``hc_pre`` and ``hc_post``: the
manifold-constrained hyper-connections around every sublayer, forward,
rematerialised forward and backward: the RMSNorm over the n streams, the
product with the three maps, sigmoid and Sinkhorn, the mix into the
sublayer's input (``hc_pre``), the streams' mix and the sublayer's output
added back (``hc_post``). None where no instruction carries either scope (a
program without hyper-connections). Layer: model step."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.device_ms(scope_reduce.of(ctx), {"hc_pre", "hc_post"})
