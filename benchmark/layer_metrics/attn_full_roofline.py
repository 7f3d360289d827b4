"""The full-attention layers' flash kernels' share of their roofline: the
required work of causal attention, forward and backward, over all
``full_attention`` layers of a step (``benchmark/work/laguna``: T(T+1)/2 pairs a
head and row) against the device time a step of the Pallas kernels whose name
stack holds the scope ``attn_full``; as ``attn_window_roofline``. Layer: kernels
(``ops/pallas_kernels.py``)."""

from benchmark.layer_metrics.attn_window_roofline import share


def read(ctx):
    return share(ctx, "attn_full", "attn_full")
