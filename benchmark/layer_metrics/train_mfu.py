"""The whole step's share of the chip's bf16 peak: required FLOPs of a step
(``benchmark/work``: forward x 3, causal attention at its required half,
recomputation not counted) x steps / window / (chips x peak), over the WHOLE
window on the host clock, idle time and all. Layer: model step."""


def read(ctx):
    if not ctx["peaks"] or not ctx["steps"]:
        return None
    rate = ctx["work"]["step_flops"] * ctx["steps"] / ctx["window_seconds"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
