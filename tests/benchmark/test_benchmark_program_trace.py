"""The reductions over what the program writes itself (`benchmark/program_trace.py`,
PR 25): on hand-made rows, on a small trace recorded on the chip with the
program's `dyn:` spans and named step programs in it
(`benchmark/fixtures/trace_v5e_spans.json.gz`: a v5e run of
`mistral-7b-w8.chat`), and in a CPU rehearsal of a traced cell."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program_trace as pt  # noqa: E402
from benchmark import readers  # noqa: E402
from benchmark import trace as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "trace_v5e_spans.json.gz")
DEV, HOST = "/device:TPU:0", "/host:CPU"
NEW = ["staged_wait_p50_ms", "sched_host_ms", "idle_pre_launch_pct", "idle_post_sync_pct", "idle_loop_pct",
       "decode_program_ms", "mixed_program_ms", "programs_per_dispatch", "frontend_busy_pct"]
LLAMA_TRAFFICS = ("chat", "chat-sat")
NOT_IN_CHAT_SAT = {"staged_wait_p50_ms.chat-sat", "mixed_program_ms.chat-sat"}


def op(name, start, dur, line=tr.OPS_LINE):
    return [DEV, line, name, start, dur]


def mark(text, start):
    return [HOST, "python3", tr.MARK + text, start, 0]


def span(name, start, dur, step, **stats):
    return [HOST, "python3", pt.DYN + name, start, dur, dict(stats, step=step)]


def hand_rows():
    """Two iterations in a slice of 20,000 ns. Step 1 (1000-9000): a decode
    window, device busy 3000-7000. Step 2 (10000-19000): a mixed step whose
    program runs 12500-15000, its sampler 16000-16500."""
    rows = [
        mark("window_open", 0), mark("window_close", 20000),
        op("%fusion.1 = bf16[32] fusion()", 3000, 4000), op("jit_decode_multi_w8(123)", 3000, 4000, tr.MODULES_LINE),
        op("jit__threefry_fold_in(9)", 2500, 100, tr.MODULES_LINE), op("%threefry.3 = u32[2] custom-call()", 2500, 100),
        op("%fusion.2 = bf16[33] fusion()", 12500, 2500), op("jit_mixed_step(77)", 12500, 2500, tr.MODULES_LINE),
        op("%sort.4 = f32[32] sort()", 16000, 500), op("jit_sample_batch(5)", 16000, 500, tr.MODULES_LINE),
        op("jit_decode_multi_w8(123)", 19500, 4000, tr.MODULES_LINE),  # runs past the slice: not counted
    ]
    dyn = [
        span("engine.loop", 500, 9000, 1), span("sched.step", 1000, 8000, 1, kind="decode_multi"),
        span("sched.plan", 1000, 1000, 1), span("sched.upload", 2000, 700, 1), span("sched.launch", 2700, 300, 1),
        span("sched.sync", 3000, 4100, 1), span("sched.emit", 7100, 1000, 1), span("sched.account", 8100, 800, 1),
        span("engine.deliver", 9100, 300, 1), span("backend.frame", 9300, 400, 1), span("http.frame", 9800, 100, 1),
        span("sched.step", 10000, 9000, 2, kind="mixed"),
        span("sched.plan", 10000, 1500, 2), span("sched.upload", 11500, 500, 2), span("sched.launch", 12000, 400, 2),
        span("sched.sample", 12400, 4400, 2), span("sched.sync", 12700, 3900, 2),  # the sync nests in the sample
        span("sched.emit", 16800, 1200, 2), span("sched.account", 18000, 900, 2),
        span("sched.step", 19200, 700, 3), span("sched.plan", 19200, 700, 3),  # an iteration that launched nothing
    ]
    return rows, dyn


def test_idle_split_sums_to_the_idle_total_and_names_each_part():
    rows, dyn = hand_rows()
    s = pt.idle_split(rows, dyn)
    assert s["window"] == 20000
    busy = tr.busy(rows)
    assert s["idle"] == round((busy["window_s"] - busy["busy_s"]) * 1e9)  # what device_idle_pct is computed from
    assert s["pre"] + s["post"] + s["loop"] + s["sync"] == s["idle"]
    # loop: 0-1000, 9000-10000, 19000-19200, 19900-20000. pre: 1000-2500, 2600-3000 (step 1); 10000-12400 (step 2); 19200-19900 (step 3).
    assert s["loop"] == 1000 + 1000 + 200 + 100
    assert s["pre"] == 1500 + 400 + 2400 + 700
    # sync: 7000-7100 (read-back after the window); 12700-15000 less the program's 12500-15000 = 0; 15000-16000; 16500-16600.
    assert s["sync"] == 100 + 1000 + 100
    # post: 7100-8900 (emit, account) and 8900-9000 (uncovered, after the launch); 12400-12500 (sample, host part
    # before its sync; the device starts at 12500); 16600-18900 and the uncovered 18900-19000.
    assert s["post"] == 1800 + 100 + 100 + 2300 + 100
    run = types.SimpleNamespace(trace_rows=rows, _dyn_rows=dyn)
    parts = [pt.idle_pct(run, p) for p in ("pre", "post", "loop", "sync")]
    assert sum(parts) == pytest.approx(100.0 * s["idle"] / 20000)


def test_named_programs_rungs_and_programs_per_dispatch():
    rows, dyn = hand_rows()
    assert [m[0] for m in pt.modules(rows)] == ["decode_multi_w8", "_threefry_fold_in", "mixed_step", "sample_batch"]
    assert pt.rung("decode_multi_w8") == 8 and pt.rung("decode_fused_sampled_w16") == 16
    assert pt.rung("decode") == 1 and pt.rung("decode_sample") == 1 and pt.rung("mixed_step") == 1
    assert pt.program_ms(rows, "decode", per_step=True) == pytest.approx(4000 / 8 / 1e6)
    assert pt.program_ms(rows, "mixed_step", per_step=False) == pytest.approx(2500 / 1e6)
    assert pt.program_ms(rows, "admit_wave", per_step=False) is None
    assert pt.programs_per_dispatch_of(rows, dyn) == pytest.approx(4 / 2)  # step 3 launched nothing
    assert pt.frontend_busy_pct_of(rows, dyn) == pytest.approx(100.0 * (300 + 300 + 100) / 20000)  # 9100-9700 merged


def test_host_work_per_dispatch_and_staged_wait_from_the_step_log():
    _, dyn = hand_rows()
    spans = [(r[2][len(pt.DYN):], r[3], r[3] + r[4], r[5]["step"], None) for r in dyn]
    # step 1: 8000 - launch 300 - sync 4100 = 3600; step 2: 9000 - 400 - 3900 = 4700; step 3 launched nothing.
    assert pt.host_ms_per_dispatch(spans, 0, 20000) == pytest.approx((3600 + 4700) / 2 / 1e6)
    assert pt.host_ms_per_dispatch(spans, 9500, 20000) == pytest.approx(4700 / 1e6)  # step 1 began before the window
    assert pt.host_ms_per_dispatch([], 0, 20000) is None
    log = types.SimpleNamespace(spans=spans, requests=[
        {"enqueued": 10.0, "arrival": 10.05}, {"enqueued": 11.0, "arrival": 11.25}, {"enqueued": 12.0, "arrival": 12.1},
        {"enqueued": 99.0, "arrival": 99.9},  # taken after the window
        {"enqueued": None, "arrival": 13.0},  # added to a bare scheduler
    ])
    flight = types.SimpleNamespace(log=log)
    run = types.SimpleNamespace(hooks=types.SimpleNamespace(engine=types.SimpleNamespace(
        scheduler=types.SimpleNamespace(flight=flight))), window=(9.0, 20.0))
    assert pt.staged_wait_ms(run) == pytest.approx(100.0)


def test_a_program_without_spans_reads_as_nothing_and_never_raises():
    """The parent of PR 25 has no step log, no `dyn:` rows and unnamed
    programs: every new reader returns None there."""
    rows, _ = hand_rows()
    bare = types.SimpleNamespace(hooks=types.SimpleNamespace(engine=types.SimpleNamespace(
        scheduler=types.SimpleNamespace(flight=types.SimpleNamespace()))), window=(0.0, 1.0),
        trace_rows=[r for r in rows if not r[2].startswith("jit_")], _dyn_rows=[])
    untraced = types.SimpleNamespace(hooks=bare.hooks, window=(0.0, 1.0), trace_rows=None)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # The sixteen of PR 25, spelled out: NEW x the two `llama` traffics, less the two that `chat-sat` never had. A
    # later cell may name a metric of its own `<one of NEW>.<its traffic>`: nothing here counts the manifest (PR 44).
    names = [f"{n}.{t}" for n in NEW for t in LLAMA_TRAFFICS if f"{n}.{t}" not in NOT_IN_CHAT_SAT]
    assert len(names) == 16 and set(names) <= {m["name"] for m in manifest["per_layer"]}
    for name in names:
        assert readers.read_metric(name, bare) is None, name
        assert readers.read_metric(name, untraced) is None, name
        spec = readers.load_metric(name)
        assert spec["reader"].startswith("program_trace.") and len(spec["unit"]) <= 16
        assert spec["layer"] in {m["layer"] for m in manifest["per_layer"] if m["name"].rsplit(".", 1)[0] not in NEW}


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.fail("the recorded trace is part of the benchmark")
    return pt.split_rows(tr.load_rows(FIXTURE))


def test_recorded_trace_holds_the_programs_spans_and_named_programs(recorded):
    rows, dyn = recorded
    assert tr.device_planes(rows) == [DEV] and dyn
    names = {r[2][len(pt.DYN):] for r in dyn}
    assert {"engine.loop", "sched.step", "sched.plan", "sched.upload", "sched.launch", "sched.sync", "sched.emit",
            "sched.account", "engine.deliver", "backend.frame", "http.frame"} <= names
    steps = [r for r in dyn if r[2] == pt.DYN + "sched.step" and "kind" in r[5]]
    assert steps and all({"step", "kind", "key", "rows", "ctx", "prefill", "decode"} <= set(r[5]) for r in steps)
    mods = pt.modules(rows)
    programs = {m[0] for m in mods}
    assert any(p.startswith("decode_multi_w") for p in programs) and not any("lambda" in p for p in programs)
    busy = tr.busy(rows)
    device = busy["busy_s"] * 1e9
    assert sum(d for n, _, d in mods if n.startswith(("decode", "mixed_step"))) >= 0.9 * device  # named programs own the chip


def test_recorded_trace_reduces_to_numbers_that_agree_with_the_outside_in_ones(recorded):
    rows, dyn = recorded
    s = pt.idle_split(rows, dyn)
    busy = tr.busy(rows)
    assert s["idle"] == pytest.approx((busy["window_s"] - busy["busy_s"]) * 1e9, abs=2)
    assert s["pre"] + s["post"] + s["loop"] + s["sync"] == s["idle"]
    assert min(s["pre"], s["post"], s["loop"]) > 0
    assert 0.8 * s["idle"] <= s["pre"] + s["post"] + s["loop"] <= s["idle"]  # little of the idle time hides in sched.sync
    # A decode program's device time per step, by name, against the host marks' bisecting (decode_step_ms).
    by_marks = [st for st in tr.steps(rows) if st["kind"] == "decode_multi"]
    per_step = 1e3 * sum(st["device_s"] for st in by_marks) / sum(int(st["key"].split(",")[0]) for st in by_marks)
    assert pt.program_ms(rows, "decode", per_step=True) == pytest.approx(per_step, rel=0.03)
    assert 5 < pt.program_ms(rows, "decode", per_step=True) < 100
    n = pt.programs_per_dispatch_of(rows, dyn)
    assert n is not None and n >= 1
    fe = pt.frontend_busy_pct_of(rows, dyn)
    assert fe is not None and 0 < fe < 100


def test_rehearsal_lists_the_new_metrics_that_need_no_device(rehearsed):
    """A CPU trace has no device plane (no "XLA Ops", no "XLA Modules"), so a
    rehearsal lists the metrics read from the step log and from the host
    spans; the idle split, the program times and programs per dispatch are
    checked on the recorded chip trace above, as `decode_step_ms` is. The
    rehearsal is the session's one (`conftest.py::rehearsed`): this test used
    to launch a second, which collided with the first in `.bench_state/`."""
    assert rehearsed["returncode"] == 0, rehearsed["stderr"][-3000:]
    last = json.loads(rehearsed["stdout"].splitlines()[-1])
    assert last["correct"] is True and last["metrics"] == {} and last["rehearsal"] is True
    assert {"staged_wait_p50_ms.chat", "sched_host_ms.chat", "frontend_busy_pct.chat"} <= set(last["metric_names"])
    device_only = {"idle_pre_launch_pct.chat", "idle_post_sync_pct.chat", "idle_loop_pct.chat", "decode_program_ms.chat",
                   "mixed_program_ms.chat", "programs_per_dispatch.chat", "decode_step_ms.chat", "device_idle_pct.chat"}
    assert not device_only & set(last["metric_names"])  # no device: no device metric, new or old
