"""The held routed experts' three grouped products' share of their roofline:
their required work at the balanced load, forward and backward, over all sparse
layers of a step (``benchmark/work/laguna.experts_work``) against the device
time a step of the instructions under the scope ``experts`` AND of XLA's own
grouped-product kernels: the TPU compiler rewrites ``ragged_dot`` into Mosaic
calls named ``%ragged-dot-<...>`` (the products and the kernel that lays out
their groups) whose metadata carries no name stack, so the scope alone would
miss what it is there to time. Recomputed products' time counts, their work
does not. None, never 0, where nothing is found. Layer: kernels."""

from benchmark import scope_reduce
from benchmark.layer_metrics.attn_window_roofline import roofline_percent


def is_expert_product(name, stack):
    return "experts" in scope_reduce.tokens(stack) \
        or name.startswith("%ragged-dot")


def read(ctx):
    reduced = scope_reduce.of(ctx)
    if reduced is None:
        return None
    ms = 1e3 * sum(row["mean_s"]
                   for name, row in reduced["instructions"].items()
                   if is_expert_product(name, row["stack"]))
    return roofline_percent(ctx["work"].get("experts"), ctx["peaks"], ms)
