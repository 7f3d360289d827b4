"""A looped model's flash kernels' share of their roofline: the required work
of causal attention, forward and backward, over all ``total_ut_steps`` x
``num_hidden_layers`` layer applications of a step (``benchmark/work/ouro``:
T(T+1)/2 pairs a head and row an application; Q, K, V, O and their gradients
moved once) against the device time a step of the Pallas kernels whose name
stack holds the scope ``attn``: forward, dQ and dK/dV of every application,
each once (a rematerialised block keeps the forward kernel's output). As
``attn_window_roofline``. Layer: kernels (``ops/pallas_kernels.py``)."""

from benchmark.layer_metrics.attn_window_roofline import share


def read(ctx):
    return share(ctx, "attn", "loop_attn")
