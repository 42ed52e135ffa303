"""The byte and FLOP functions against one hand-worked decode step, the peaks
table, and the parent's counts on a grid, which moving `decode_step_cost` into
the family had to keep."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import families, roofline  # noqa: E402

LLAMA = families.load("llama")
# Counts of the parent commit (428f41a, PR 26), taken by running its `roofline.decode_step_cost` before it moved.
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "frozen_parent.json")) as _f:
    FROZEN = json.load(_f)["roofline"]


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
    c = LLAMA.decode_step_cost(cfg("mistral-7b-w8"), "int8", 32, 19200)
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
    one = LLAMA.decode_step_cost(c, "bfloat16", 1, 100)
    full = LLAMA.decode_step_cost(c, "bfloat16", 32, 3200)
    expert = 3 * 4096 * 14336 * 2
    assert LLAMA.experts_reached(8, 2, 1) == pytest.approx(2.0)
    assert LLAMA.experts_reached(8, 2, 32) == pytest.approx(8.0, abs=0.01)
    assert full["weight_bytes"] - one["weight_bytes"] == pytest.approx(3 * expert * (LLAMA.experts_reached(8, 2, 32) - 2.0))
    # a row computes its two experts, whatever the batch reaches
    per_row = lambda r, ctx: (LLAMA.decode_step_cost(c, "bfloat16", r, ctx)["flops"] - 3 * 4 * 32 * 128 * ctx) / r  # noqa: E731
    assert per_row(1, 100) == pytest.approx(per_row(32, 3200))


def test_an_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.min_seconds({"flops": 1.0, "bytes": 1.0}, "TPU v9")


@pytest.mark.parametrize("case", sorted(FROZEN), ids=lambda c: c.replace("/", "-"))
def test_the_family_counts_what_the_parent_counted(case):
    """(configuration, weight type, rows, context tokens) -> the parent's FLOPs and bytes, exactly."""
    name, weight_dtype, rows, ctx = case.split("/")
    c = cfg(name)
    cost = families.load(c["family"]).decode_step_cost(c, weight_dtype, int(rows), int(ctx))
    assert [cost["flops"], cost["bytes"]] == FROZEN[case]
