"""The flash-attention forward kernel's share of its roofline: the required
work of causal attention forward over all layers of a step
(``benchmark/work/transformer``: the causal half of the square; Q, K, V read
and O written once) against the device time of the forward kernel's events.
Layer: kernels (``ops/pallas_kernels.py``).

Which events: the trace names a device event by its whole HLO instruction, and
a Pallas kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``; the text does not carry
``kernel_name``. One look at a real trace (PR 25) shows the forward
``_flash_kernel`` as ``%jvp__.<n>`` (JAX's name stack: the kernel runs under the
``custom_vjp``'s forward) with results ``(bf16 O, f32 lse)``, and the two
backward kernels as ``%transpose_jvp___.<n>``. So: forward = a tpu_custom_call
whose instruction name starts with ``%jvp``. Stable kernel names are asked of
the next ``tracing`` issue (PERF.md, Open questions).
"""

from benchmark import trace_reduce


def is_flash_fwd(name):
    return 'custom_call_target="tpu_custom_call"' in name \
        and name.startswith("%jvp")


def read(ctx):
    work = ctx["work"].get("flash_fwd")
    if work is None:
        return None
    return trace_reduce.roofline_share(ctx["trace"], is_flash_fwd, work,
                                       ctx["peaks"])
