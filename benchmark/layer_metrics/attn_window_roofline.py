"""The window-attention layers' flash kernels' share of their roofline: the
required work of causal attention inside a window of W keys, forward and
backward, over all ``sliding_attention`` layers of a step
(``benchmark/work/laguna``: W(W+1)/2 + (T-W)W pairs a head and row; Q, K, V, O
and their gradients moved once) against the device time a step of the Pallas
kernels whose name stack holds the scope ``attn_window``: the forward, the
forward run again where the block is rematerialised (time counted, work not),
dQ and dK/dV. Layer: kernels (``ops/pallas_kernels.py``).

The kernels are found by the scope in the name stack (``scope_reduce``), not by
the instruction's name: under ``jax.checkpoint`` the backward's kernels are
named ``%block.attn_window.<n>``."""

from benchmark import scope_reduce

SCOPE, WORK = "attn_window", "attn_window"


def roofline_percent(work, peaks, ms):
    """``work`` (``{"flops", "bytes"}``) at the chip's peaks, as a percent of
    the ``ms`` it took; None where any of the three is missing."""
    if not work or not peaks or not ms:
        return None
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * 1e3 * least / ms


def share(ctx, scope, work):
    ms = scope_reduce.device_ms(scope_reduce.of(ctx), {scope}, kernels=True)
    return roofline_percent(ctx["work"].get(work), ctx["peaks"], ms)


def read(ctx):
    return share(ctx, SCOPE, WORK)
