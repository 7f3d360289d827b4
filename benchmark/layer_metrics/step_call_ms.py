"""Host milliseconds a step inside the program's span ``dl4j:lm.step_call``
(``TransformerLM.fit_batch``: the call of the jitted step alone, which returns
once the step is enqueued). With ``h2d_ms`` it splits ``host_dispatch_ms``.
Layer: dispatch."""

from benchmark import scope_reduce


def read(ctx):
    return scope_reduce.span_ms_per(scope_reduce.of(ctx), "lm.step_call",
                                    "lm.step_call")
