"""XLA backend compiles inside the measured window (``benchmark/compile_count``);
expected 0. Layer: dispatch."""


def read(ctx):
    return ctx["compiles_in_window"]
