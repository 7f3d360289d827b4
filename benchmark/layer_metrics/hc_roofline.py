"""The hyper-connections' maps' share of their roofline: their required work,
forward and backward, over the two sublayers of every layer of a step
(``benchmark/work/xing.hc_work``: the streams read and written once a pass,
the maps' products and mixes; bound by bytes) against the device time a step
under the scopes ``hc_pre`` and ``hc_post`` (``hc_ms``), the rematerialised
forward's time counted and its work not. None where nothing is found (a
program without hyper-connections, or a job that counts no such work).
Layer: kernels."""

from benchmark.layer_metrics import hc_ms
from benchmark.layer_metrics.attn_window_roofline import roofline_percent


def read(ctx):
    return roofline_percent(ctx["work"].get("hc"), ctx["peaks"],
                            hc_ms.read(ctx))
