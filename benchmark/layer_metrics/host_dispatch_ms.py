"""Host milliseconds per step inside the call that dispatches one update, no
sync: the benchmark's own ``fit_batch`` span for ``lm_train``; the program's
``train.step_seconds`` histogram (``_fit_one``) for ``graph_fit``. Layer:
dispatch."""


def read(ctx):
    if not ctx["steps"]:
        return None
    return 1e3 * ctx["dispatch_seconds"] / ctx["steps"]
