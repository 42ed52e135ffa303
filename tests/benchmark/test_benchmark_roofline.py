"""The byte and FLOP functions against one hand-worked decode step, and the peaks table."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import roofline  # noqa: E402


def cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_mistral_w8_step_by_hand():
    # 32 rows, contexts summing to 19200 tokens. By hand, per layer:
    #   attention weights 4096*4096*2 + 4096*1024*2 = 41,943,040 int8 bytes, + (4096+1024+1024+4096)*4 of scales
    #   mlp weights 3*4096*14336 = 176,160,768 int8 bytes, + (14336+14336+4096)*4 of scales
    #   two norm vectors 2*4096*2
    per_layer = 41_943_040 + 10_240 * 4 + 176_160_768 + 32_768 * 4 + 2 * 4096 * 2
    weights = 32 * per_layer + 4096 * 32768 * 2 + 4096 * 2  # + bf16 head + final norm
    kv = 32 * (2 * 8 * 128 * 2) * (19200 + 32)  # every context row read, one row written per sequence
    io = 32 * (4096 * 2 + 32768 * 4)
    c = roofline.decode_step_cost(cfg("mistral-7b-w8"), "int8", 32, 19200)
    assert c["weight_bytes"] == weights
    assert c["kv_bytes"] == kv
    assert c["bytes"] == weights + kv + io
    params_layer = 41_943_040 + 176_160_768
    flops = 32 * (32 * 2 * params_layer + 2 * 4096 * 32768) + 32 * 4 * 32 * 128 * 19200
    assert c["flops"] == flops
    least = roofline.min_seconds(c, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx((weights + kv + io) / 819e9)
    assert 0.0115 < least["seconds"] < 0.0125  # 7.25 GB of weights alone are 8.9 ms


def test_mixtral_counts_only_the_experts_a_step_reaches():
    c = cfg("mixtral-8x7b-d3")
    one = roofline.decode_step_cost(c, "bfloat16", 1, 100)
    full = roofline.decode_step_cost(c, "bfloat16", 32, 3200)
    expert = 3 * 4096 * 14336 * 2
    assert roofline.experts_reached(8, 2, 1) == pytest.approx(2.0)
    assert roofline.experts_reached(8, 2, 32) == pytest.approx(8.0, abs=0.01)
    assert full["weight_bytes"] - one["weight_bytes"] == pytest.approx(3 * expert * (roofline.experts_reached(8, 2, 32) - 2.0))
    # a row computes its two experts, whatever the batch reaches
    per_row = lambda r, ctx: (roofline.decode_step_cost(c, "bfloat16", r, ctx)["flops"] - 3 * 4 * 32 * 128 * ctx) / r  # noqa: E731
    assert per_row(1, 100) == pytest.approx(per_row(32, 3200))


def test_an_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.min_seconds({"flops": 1.0, "bytes": 1.0}, "TPU v9")
