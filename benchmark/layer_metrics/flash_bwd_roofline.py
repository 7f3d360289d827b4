"""The flash-attention backward's share of its roofline: the required work of
causal attention backward over all layers of a step (dV, dP, dQ, dK over the
causal pairs, the recomputed scores not counted; Q, K, V, O, dO read and dQ, dK,
dV written once) against the summed device time of ``_flash_dq_kernel`` and
``_flash_dkv_kernel``. Layer: kernels (``ops/pallas_kernels.py``).

Which events: see ``flash_fwd_roofline``; the backward kernels are the
tpu_custom_calls whose instruction name starts with ``%transpose`` (JAX's name
stack for the ``custom_vjp``'s backward). Both kernels of a layer count as one
run of the set: each has an instruction of its own per layer.
"""

from benchmark import trace_reduce


def is_flash_bwd(name):
    return 'custom_call_target="tpu_custom_call"' in name \
        and name.startswith("%transpose")


def read(ctx):
    work = ctx["work"].get("flash_bwd")
    if work is None:
        return None
    return trace_reduce.roofline_share(ctx["trace"], is_flash_bwd, work,
                                       ctx["peaks"])
