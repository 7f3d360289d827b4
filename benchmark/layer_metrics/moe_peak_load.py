"""The fullest held expert's load against the even one, over the window's
steps: the program's counters ``moe.peak_group_rows`` (a layer's and step's
largest held expert's assignments, summed over layers and steps) /
``moe.even_group_rows`` (``moe.local_rows`` over the held experts' count: what
each would take were the assignments that met a held expert spread evenly).
1.0 when the held experts are evenly loaded; above it the straggler an
expert-parallel deployment would wait for. None where the counters are not
there (a program before them, a model without experts). Layer: model step."""


def read(ctx):
    counters = ctx["counters"] or {}
    peak, even = (counters.get("moe.peak_group_rows"),
                  counters.get("moe.even_group_rows"))
    if not peak or not even:
        return None
    return peak / even
