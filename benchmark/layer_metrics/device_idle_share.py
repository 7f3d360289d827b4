"""1 - (union of the device-op intervals) / (traced window), from the
profiler's trace, averaged over the chips. Layer: device."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
