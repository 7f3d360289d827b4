"""The FLOP and byte functions against counts made by hand."""

import json
import os

import pytest

from benchmark.references.resnet_v1 import plan
from benchmark.work import resnet_v1, transformer

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_layer_by_hand():
    # one row of 1024 tokens, d 1024, 16 heads of 64, d_ff 4096
    # products: 2 FLOP x 1024 tokens x (1024*3072 + 1024*1024 + 2*1024*4096)
    assert transformer.block_matmul_fwd_flops(1024, 1024, 4096) \
        == 2 * 1024 * 12_582_912 == 25_769_803_776
    # causal pairs 1024*1025/2 = 524,800; QK^T and PV: 2 x 2 x 64 per pair
    assert transformer.causal_attention_fwd_flops(1, 16, 1024, 64) \
        == 16 * 256 * 524_800 == 2_149_580_800
    assert transformer.causal_attention_bwd_flops(1, 16, 1024, 64) \
        == 2 * 2_149_580_800
    # Q, K, V read and O written, bf16: 4 x 16 x 1024 x 64 x 2 bytes
    assert transformer.attention_fwd_bytes(1, 16, 1024, 64, 2) == 8_388_608
    assert transformer.attention_bwd_bytes(1, 16, 1024, 64, 2) == 16_777_216


def test_causal_attention_is_under_the_full_square():
    full = 16 * 2 * 2 * 64 * 1024 * 1024
    got = transformer.causal_attention_fwd_flops(1, 16, 1024, 64)
    assert 0.5 * full < got < 0.51 * full


def test_gpt2_medium_step_total():
    c = _config("gpt2-medium")
    assert transformer.d_ff(c) == 4096
    tokens = 8 * 1024
    fwd = (24 * (2 * tokens * 12_582_912 + 8 * 2_149_580_800)
           + 2 * tokens * 1024 * 50257)
    assert transformer.train_step_flops(c, 8, 1024) == 3 * fwd
    fa, ba, fb, bb = transformer.attention_work(c, 8, 1024)
    assert (fa, ba) == (24 * 8 * 2_149_580_800, 24 * 8 * 8_388_608)
    assert (fb, bb) == (2 * fa, 2 * ba)


def test_resnet_bottleneck_by_hand():
    # stage 0, block 1 at 56x56: 1x1 256->64, 3x3 64->64 (pad 1), 1x1 64->256
    assert resnet_v1.conv_fwd_flops(1, 1, 1, 0, 256, 64, 56, 56) \
        == 2 * 56 * 56 * 256 * 64 == 102_760_448
    assert resnet_v1.conv_fwd_flops(1, 3, 1, 1, 64, 64, 56, 56) \
        == 2 * 56 * 56 * 9 * 64 * 64 == 231_211_008
    # the stem: 7x7/2 pad 3 on 224 -> 112
    assert resnet_v1.conv_fwd_flops(1, 7, 2, 3, 3, 64, 224, 224) \
        == 2 * 112 * 112 * 49 * 3 * 64


def test_resnet50_plan_and_totals():
    c = _config("resnet50")
    convs, blocks, c_last = plan(c)
    assert len(convs) == 53 and len(blocks) == 16 and c_last == 2048
    by_name = {cv[0]: cv for cv in convs}
    assert by_name["s0b1_b"][1:] == (3, 1, 1, 64, 64, 56, 56)
    assert by_name["s3b0_a"][1:] == (1, 2, 0, 1024, 512, 14, 14)
    fwd = sum(resnet_v1.conv_fwd_flops(1, *cv[1:]) for cv in convs)
    # ResNet-50 v1 (stride in the first 1x1): 3.86 G multiply-adds
    assert fwd == 7_711_850_496
    stem = resnet_v1.conv_fwd_flops(1, *convs[0][1:])
    assert resnet_v1.conv_train_flops(c, 1) == 3 * fwd - stem
    assert resnet_v1.dense_train_flops(c, 128) == 3 * 2 * 128 * 2048 * 1000
    assert resnet_v1.train_step_flops(c, 128) == pytest.approx(2.93e12,
                                                               rel=2e-3)
