"""Required work of a ResNet v1 training step, counted from the layer shapes
the configuration gives (``references/resnet_v1.plan``), 2 FLOP per
multiply-add. Forward: every convolution and the classifier. Backward: the
weight gradient of each, and the input gradient of each but the stem, whose
input is the image and needs none. Batch normalisation, ReLU, pooling and the
updater are bandwidth, not FLOPs, and are not counted.
"""

from __future__ import annotations

from benchmark.references.resnet_v1 import plan


def conv_fwd_flops(batch, kernel, stride, pad, c_in, c_out, h_in, w_in):
    h_out = (h_in + 2 * pad - kernel) // stride + 1
    w_out = (w_in + 2 * pad - kernel) // stride + 1
    return 2 * batch * h_out * w_out * kernel * kernel * c_in * c_out


def conv_train_flops(config, batch):
    """Forward + weight gradients + input gradients (all but the stem's)."""
    convs, _, _ = plan(config)
    total = 0
    for i, (_, k, s, p, ci, co, h, w) in enumerate(convs):
        fwd = conv_fwd_flops(batch, k, s, p, ci, co, h, w)
        total += fwd * (2 if i == 0 else 3)
    return total


def dense_train_flops(config, batch):
    _, _, c_last = plan(config)
    return 3 * 2 * batch * c_last * config["n_classes"]


def train_step_flops(config, batch):
    return conv_train_flops(config, batch) + dense_train_flops(config, batch)
