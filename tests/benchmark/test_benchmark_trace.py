"""The reductions from trace to numbers, on a small trace recorded on the chip
(`benchmark/fixtures/trace_v5e_small.json.gz`: rows of a v5e run of
`mistral-7b-w8.chat`, PR 24) and on hand-made rows."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "trace_v5e_small.json.gz")
DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(name, start, dur, line=tr.OPS_LINE):
    return [DEV, line, name, start, dur]


def mark(text, start, dur=0):
    return [HOST, "python3", tr.MARK + text, start, dur]


def test_interval_union_merges_overlaps_and_nesting():
    assert tr.union_ns([]) == 0
    assert tr.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert tr.union_ns([(5, 6), (0, 10)]) == 10


def test_stable_names_drop_instance_numbers():
    assert tr.stable_name("%fusion.123") == "fusion"
    assert tr.stable_name("%reshape.641 = bf16[8192,128,1024]{2,1,0:T(8,128)(2,1)} reshape(bf16[8192,128,8,128] %bitcast)") == "reshape"
    assert tr.stable_name("_ragged_paged_attention") == "_ragged_paged_attention"
    assert tr.stable_name("convolution_convert_fusion.7.1") == "convolution_convert_fusion"


def hand_rows():
    return [
        mark("window_open", 1000), mark("window_close", 11000),
        mark("span|scheduler.step", 1500, 7000),
        mark("exec|decode_multi|8,32,16|rows=20|ctx=9000", 2000),
        op("%while.57 = (s32[], bf16[32,4096]) while(...)", 2100, 3000),  # encloses the three below
        op("%fusion.1 = bf16[32,4096] fusion(...)", 2100, 1000), op("%ragged_paged_attention.8 = custom-call()", 3100, 1500),
        op("%fusion.2 = bf16[32] fusion()", 4600, 400),
        op("jit_step", 2100, 3000, line=tr.MODULES_LINE),  # an enclosing span: never counted as busy
        mark("done|decode|tokens=160|kv=72000|passes=8.0|dur=0.004", 6000),
        op("fusion.9", 9000, 1000),
        op("fusion.77", 500, 400),  # before the window opens: clipped away
    ]


def test_busy_idle_ops_and_gaps_on_hand_made_rows():
    rows = hand_rows()
    b = tr.busy(rows)
    assert b["window_s"] == pytest.approx(10000e-9) and b["chips"] == 1
    assert b["busy_s"] == pytest.approx((3000 + 1000) * 1e-9)  # 2100-5100 merged, 9000-10000
    tops = dict((k, v) for k, v in tr.top_ops(rows))
    assert tops["fusion"] == pytest.approx((1000 + 400 + 1000) * 1e-9)  # fusion.1, fusion.2 and fusion.9
    assert tops["ragged_paged_attention"] == pytest.approx(1500e-9)
    assert tops["while"] == pytest.approx(100e-9)  # self time: 3000 less its children's 2900
    assert sum(tops.values()) == pytest.approx(b["busy_s"])  # nothing counts twice
    gaps = tr.idle_gaps(rows)
    assert gaps[0] == ["inside_scheduler.step>after_exec:decode_multi", pytest.approx(3900e-9)]  # 5100-9000: the device is done, the host still between exec and done
    assert ["outside_scheduler.step", pytest.approx(1100e-9)] in gaps  # 1000-2100, before the span
    (step,) = tr.steps(rows)
    assert step["kind"] == "decode_multi" and step["key"] == "8,32,16" and step["rows"] == 20
    assert step["tokens"] == 160 and step["device_s"] == pytest.approx(3000e-9) and step["n_ops"] == 4


def test_no_device_plane_reads_as_nothing():
    rows = [mark("window_open", 0), mark("window_close", 100)]
    assert tr.busy(rows)["busy_s"] is None and tr.idle_gaps(rows) == [] and tr.steps(rows) == []


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.fail("the recorded trace is part of the benchmark")
    return tr.load_rows(FIXTURE)


def test_recorded_trace_reduces_to_sane_numbers(recorded):
    assert tr.device_planes(recorded) == [DEV]
    b = tr.busy(recorded)
    assert 0 < b["busy_s"] <= b["window_s"]
    ops = tr.top_ops(recorded)
    assert 1 <= len(ops) <= 10 and all(s > 0 for _, s in ops)
    assert sum(s for _, s in ops) <= b["busy_s"] + 1e-6  # self times: nothing counts twice
    steps = tr.steps(recorded)
    assert steps and all(s["device_s"] > 0 and s["n_ops"] > 0 for s in steps)
    assert {s["kind"] for s in steps} <= {"decode_multi", "mixed", "prefill", "admit", "decode", "decode_sample"}
    gaps = tr.idle_gaps(recorded)
    assert gaps and all(name.startswith(("inside_", "outside_")) for name, _ in gaps)
    assert sum(s for _, s in gaps) <= b["window_s"] - b["busy_s"] + 1e-6
