"""Host milliseconds a step inside the program's span ``dl4j:lm.h2d``
(``TransformerLM.fit_batch``: the ``jnp.asarray`` of the token batch, its two
slices, the ``device_put``s under a data sharding): the span's sum over the
trace over the ``dl4j:lm.step_call`` spans traced. With ``step_call_ms`` it
splits ``host_dispatch_ms``. Layer: dispatch."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.span_ms_per(scope_reduce.of(ctx), "lm.h2d",
                                    "lm.step_call")
