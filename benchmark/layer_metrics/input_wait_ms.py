"""Host milliseconds per step that ``fit()`` blocked on the prefetch queue: the
window's sum of the program's ``prefetch.consumer_wait_seconds`` histogram over
the steps. Layer: input pipeline. Read where the driver reports the counter."""


def read(ctx):
    waited = ctx["counters"].get("input_wait_s")
    if waited is None or not ctx["steps"]:
        return None
    return 1e3 * waited / ctx["steps"]
