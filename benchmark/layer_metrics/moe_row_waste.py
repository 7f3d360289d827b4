"""Rows the grouped expert products ran over (the static row buffer, padding
and all) for each assignment that met an expert held here, over the window's
steps: the program's counters ``moe.rows_computed`` / ``moe.local_rows``. 1.0
when nothing is padded. Layer: model step."""


def read(ctx):
    counters = ctx["counters"] or {}
    local = counters.get("moe.local_rows")
    if not local:
        return None
    return counters.get("moe.rows_computed", 0) / local
