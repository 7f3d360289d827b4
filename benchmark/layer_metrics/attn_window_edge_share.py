"""Of the live grid steps of the flash kernels' window walks, the share that
takes the ``edge`` branch (every tile of scores masked by position) and not
the unmasked ``full`` one: the program's gauges ``flash.window_steps_edge`` /
``flash.window_steps_live``, set where the step was traced, in percent. 100
where the window is one block (every live block crosses the diagonal or the
window's edge), 56 / 252 = 22.2 at T 16384, window 4096 in blocks of 512. From
shapes, not from time: it says how much of ``attn_window_roofline`` the
masking can explain. None where the gauges are not there. Layer: kernels."""


def read(ctx):
    counters = ctx["counters"] or {}
    live = counters.get("flash.window_steps_live")
    if not live:
        return None
    return 100.0 * counters.get("flash.window_steps_edge", 0) / live
