"""Device-truth profiling plane tests (PR 15).

Four layers, matching the subsystem's split:

- **trace parser** — pure-stdlib Chrome trace-event attribution against
  hand-built fixtures: exact per-kernel durations, interval-union busy time
  (nested/overlapping events never double-count), host-lane exclusion, the
  ops-thread filter, the no-device-lane fallback, and tolerance for
  truncated gzip / truncated JSON / outright garbage (a profiler artifact
  cut mid-write must yield its prefix, not a crash);
- **continuous sampler** — duty-cycle and rate-limit gating under an
  injected clock (first-window grace, max_duty interval clamp, force
  bypass), busy-yield accounting, error accounting, and one full window
  against a stub profiler writing a fixture artifact;
- **capture serialization** — DeviceProfiler's one-capture-at-a-time
  invariant under real thread races: wait=False gets a structured busy,
  wait=True queues, collisions are counted, start/stop never interleave;
- **measured truth → capacity** — record_measured_window's derived gauges,
  the cost-model calibration sanity band, and the ProfiledCapacityModel
  replay: an autoscale decision table that starts on wrong declared rates
  and converges to the measured-rate oracle.
"""

import gzip
import json
import os
import threading
import time

import pytest

from dynamo_tpu.engine.flight_recorder import FlightRecorder, StepCostModel
from dynamo_tpu.planner.controller import (
    DECODE,
    PREFILL,
    AutoscaleController,
    ControllerConfig,
    FleetView,
    ProfiledCapacityModel,
    StaticCapacityModel,
    WorkerView,
)
from dynamo_tpu.planner.planner_core import ObservedLoad
from dynamo_tpu.runtime.profiling import (
    ContinuousProfileConfig,
    ContinuousProfiler,
    DeviceProfiler,
    load_trace_dir,
    parse_trace_bytes,
    parse_trace_events,
)


# --- fixture builders ---------------------------------------------------------
def _pmeta(pid, name):
    return {"ph": "M", "pid": pid, "name": "process_name", "args": {"name": name}}


def _tmeta(pid, tid, name):
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": name}}


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur}


def device_fixture_events():
    """One TPU lane with an ops thread + a modules thread, one host lane.

    Kernel lane (7, 1): attention launches at [0,100) [200,250) [300,350) and a
    sampler fusion at [400,425) — busy union 225us, wall span 425us.
    """
    return [
        _pmeta(7, "/device:TPU:0 (fixture)"),
        _tmeta(7, 1, "XLA Ops"),
        _tmeta(7, 2, "XLA Modules"),
        _pmeta(99, "python"),
        _tmeta(99, 1, "main"),
        _x(7, 1, "ragged_paged_attention(layer)", 0, 100),
        _x(7, 1, "ragged_paged_attention(layer)", 200, 50),
        _x(7, 1, "ragged_paged_attention(layer)", 300, 50),
        _x(7, 1, "fusion.sample_rows", 400, 25),
        _x(7, 2, "jit_decode_window", 0, 425),  # module span, not a kernel
        _x(99, 1, "host_busy_loop", 0, 1000),   # host lane, excluded
    ]


FIXTURE_BUSY_US = 225.0
FIXTURE_WALL_US = 425.0


# --- trace parser -------------------------------------------------------------
def test_fixture_exact_attribution():
    s = parse_trace_events(device_fixture_events())
    assert s.device_lane_found
    assert not s.truncated
    assert s.events_total == 6          # every ph=="X", host included
    assert s.kernel_events == 4         # ops-thread events only
    assert s.device_lanes == 1
    assert s.device_time_us == FIXTURE_BUSY_US
    assert s.wall_us == FIXTURE_WALL_US
    attn = s.kernels["ragged_paged_attention(layer)"]
    assert (attn.count, attn.total_us, attn.max_us) == (3, 200.0, 100.0)
    sample = s.kernels["fusion.sample_rows"]
    assert (sample.count, sample.total_us) == (1, 25.0)
    assert s.launch_count("ragged_paged_attention") == 3
    top = s.top(2)
    assert top[0]["name"] == "ragged_paged_attention(layer)"
    assert top[0]["share"] == pytest.approx(200.0 / 225.0, abs=1e-3)
    assert s.top_share() == pytest.approx(200.0 / 225.0)


def test_nested_and_overlapping_events_union_once():
    """Nested sub-events and overlapping launches in one lane must not
    double-count busy time — attribution per kernel still sums raw."""
    events = [
        _pmeta(7, "/device:TPU:0"),
        _x(7, 1, "outer_fusion", 0, 100),
        _x(7, 1, "nested.child", 10, 30),    # inside outer
        _x(7, 1, "tail_overlap", 90, 30),    # overlaps outer's tail
    ]
    s = parse_trace_events(events)
    assert s.device_time_us == 120.0          # union of [0,100)∪[10,40)∪[90,120)
    assert s.kernels["outer_fusion"].total_us == 100.0
    assert s.kernels["nested.child"].total_us == 30.0
    # Two parallel lanes ADD: same events split across tids double the union.
    par = [
        _pmeta(7, "/device:TPU:0"),
        _x(7, 1, "k", 0, 100),
        _x(7, 2, "k", 0, 100),
    ]
    assert parse_trace_events(par).device_time_us == 200.0


def test_thread_filter_requires_named_ops_threads():
    """The ops-thread filter only applies when the device pid HAS a named
    ops thread; device fixtures without thread metadata keep everything."""
    bare = [
        _pmeta(7, "/device:TPU:0"),
        _x(7, 1, "kernel_a", 0, 10),
        _x(7, 5, "kernel_b", 20, 10),
    ]
    s = parse_trace_events(bare)
    assert s.kernel_events == 2 and s.device_time_us == 20.0
    # With an ops thread present, other device threads are module/host noise.
    s2 = parse_trace_events(device_fixture_events())
    assert "jit_decode_window" not in s2.kernels
    assert "host_busy_loop" not in s2.kernels


def test_no_device_lane_falls_back_to_all_events():
    """CPU CI traces have no /device: lane — the parser degrades to
    'everything is a kernel' rather than an empty summary."""
    events = [
        _pmeta(1, "python"),
        _x(1, 1, "cpu_fusion", 0, 40),
        _x(1, 2, "cpu_copy", 100, 10),
    ]
    s = parse_trace_events(events)
    assert not s.device_lane_found
    assert s.kernel_events == 2
    assert s.device_time_us == 50.0


def test_malformed_events_skipped():
    events = [
        _pmeta(7, "/device:TPU:0"),
        _x(7, 1, "good", 0, 10),
        _x(7, 1, "negative_dur", 20, -5),
        {"ph": "X", "pid": 7, "tid": 1, "name": "bad_ts", "ts": "nan?", "dur": "x"},
        "not even a dict",
        {"ph": "B", "pid": 7, "tid": 1, "name": "begin_only", "ts": 5},
    ]
    s = parse_trace_events(events)
    assert list(s.kernels) == ["good"]
    assert s.device_time_us == 10.0


def _doc_bytes(events):
    return json.dumps({"traceEvents": events, "displayTimeUnit": "ns"}).encode()


def test_gzip_roundtrip_matches_plain():
    raw = _doc_bytes(device_fixture_events())
    plain = parse_trace_bytes(raw)
    gz = parse_trace_bytes(gzip.compress(raw))
    assert not gz.truncated
    assert gz.device_time_us == plain.device_time_us == FIXTURE_BUSY_US
    assert gz.launch_count("ragged_paged_attention") == 3


def test_truncated_json_recovers_prefix_exactly():
    """Cut the document right after the second attention launch: the scanner
    must recover exactly the events serialized before the cut."""
    events = device_fixture_events()
    parts = [json.dumps(e) for e in events]
    keep = 7  # metadata (5) + first two attention launches
    text = '{"traceEvents": [' + ", ".join(parts[:keep]) + ", " + parts[keep][:10]
    s = parse_trace_bytes(text.encode())
    assert s.truncated
    assert s.kernel_events == 2
    assert s.device_time_us == 150.0  # [0,100) + [200,250)
    assert s.launch_count("ragged_paged_attention") == 2


def test_truncated_gzip_yields_prefix_not_crash():
    data = gzip.compress(_doc_bytes(device_fixture_events()))
    s = parse_trace_bytes(data[: len(data) // 2])
    assert s.truncated
    assert s.kernel_events <= 4
    assert s.device_time_us <= FIXTURE_BUSY_US


def test_garbage_bytes_yield_empty_summary():
    s = parse_trace_bytes(b"\x00\xffnot a trace at all")
    assert s.truncated
    assert s.kernel_events == 0 and s.device_time_us == 0.0
    assert s.top() == [] and s.top_share() == 0.0


def test_load_trace_dir_newest_artifact_wins(tmp_path):
    assert load_trace_dir(str(tmp_path)) is None           # empty dir
    assert load_trace_dir(str(tmp_path / "missing")) is None
    old = tmp_path / "plugins" / "profile" / "run1"
    old.mkdir(parents=True)
    (old / "host.trace.json").write_bytes(_doc_bytes([
        _pmeta(7, "/device:TPU:0"), _x(7, 1, "old_kernel", 0, 10),
    ]))
    new = tmp_path / "plugins" / "profile" / "run2"
    new.mkdir(parents=True)
    p = new / "host.trace.json.gz"
    p.write_bytes(gzip.compress(_doc_bytes(device_fixture_events())))
    now = time.time()
    os.utime(old / "host.trace.json", (now - 100, now - 100))
    os.utime(p, (now, now))
    s = load_trace_dir(str(tmp_path))
    assert s is not None and "old_kernel" not in s.kernels
    assert s.launch_count("ragged_paged_attention") == 3


# --- continuous sampler gating under an injected clock ------------------------
class _StubProfiler:
    """DeviceProfiler stand-in: no jax, no sleeping — returns a canned
    status, writing a fixture artifact on the "ok" path."""

    def __init__(self, tmp_path, mode="ok", events=None):
        self.tmp_path = tmp_path
        self.mode = mode
        self.events = events if events is not None else device_fixture_events()
        self.calls = []
        self._seq = 0

    def capture(self, seconds, label="manual", wait=False):
        self.calls.append((seconds, label, wait))
        if self.mode == "busy":
            return {"status": "busy"}
        if self.mode == "error":
            return {"status": "error: RuntimeError: no backend"}
        self._seq += 1
        d = os.path.join(str(self.tmp_path), f"cap_{self._seq}")
        os.makedirs(d)
        with open(os.path.join(d, "host.trace.json"), "w") as f:
            json.dump({"traceEvents": self.events}, f)
        return {"status": "ok", "path": d, "seconds": seconds, "label": label}


def _clocked(profiler, cfg=None, **kw):
    t = [0.0]
    cont = ContinuousProfiler(profiler, cfg or ContinuousProfileConfig(),
                              clock=lambda: t[0], **kw)
    return cont, t


def test_first_window_waits_full_interval(tmp_path):
    cont, t = _clocked(_StubProfiler(tmp_path))
    assert cont.effective_interval_s == 30.0
    assert not cont.due(0.0) and not cont.due(29.9)
    assert cont.due(30.0)
    assert cont.sample_once(now=10.0) == {"status": "not_due"}
    assert cont.windows_total == 0 and not cont.profiler.calls


def test_max_duty_clamps_interval():
    cfg = ContinuousProfileConfig(window_s=0.5, interval_s=1.0, max_duty=0.02)
    cont, _ = _clocked(_StubProfiler("/tmp"), cfg)
    assert cont.effective_interval_s == 25.0  # 0.5 / 0.02 floors the 1s ask
    assert cont.duty_cycle == pytest.approx(0.02)
    # Defaults sit well inside the cap.
    d, _ = _clocked(_StubProfiler("/tmp"))
    assert d.duty_cycle == pytest.approx(0.25 / 30.0)
    assert d.duty_cycle <= d.config.max_duty


def test_force_bypasses_gate_and_rearms_it(tmp_path):
    cont, t = _clocked(_StubProfiler(tmp_path))
    rec = cont.sample_once(now=10.0, force=True)
    assert rec["status"] == "ok"
    assert cont.windows_total == 1
    # The forced window reset the limiter: next one is due at 10 + interval.
    assert not cont.due(35.0) and cont.due(40.0)
    assert cont.sample_once(now=20.0) == {"status": "not_due"}


def test_busy_profiler_yields_and_counts(tmp_path):
    cont, _ = _clocked(_StubProfiler(tmp_path, mode="busy"))
    assert cont.sample_once(force=True) == {"status": "skipped_busy"}
    assert cont.skipped_busy_total == 1 and cont.errors_total == 0
    assert cont.windows_total == 0
    # The sampler never queues: the stub saw wait=False.
    assert cont.profiler.calls[-1][2] is False


def test_capture_error_counts_not_raises(tmp_path):
    cont, _ = _clocked(_StubProfiler(tmp_path, mode="error"))
    res = cont.sample_once(force=True)
    assert res["status"].startswith("error")
    assert cont.errors_total == 1 and cont.windows_total == 0


def test_full_window_record_and_sink(tmp_path):
    probes = [(1e12, 2e12, 0.20), (2e12, 3e12, 0.43)]
    sunk = []
    stub = _StubProfiler(tmp_path)
    cont, _ = _clocked(stub, cost_probe=lambda: probes.pop(0),
                       sink=sunk.append)
    rec = cont.sample_once(force=True)
    assert rec["status"] == "ok"
    assert rec["wall_s"] == 0.25
    assert rec["device_time_s"] == pytest.approx(FIXTURE_BUSY_US / 1e6)
    assert rec["flops"] == pytest.approx(1e12)
    assert rec["bytes"] == pytest.approx(1e12)
    assert rec["step_seconds"] == pytest.approx(0.23)
    assert rec["device_lane_found"] and not rec["truncated"]
    assert sunk == [rec]
    # keep_artifacts defaults off: the capture dir is gone after parsing.
    assert not os.path.exists(os.path.join(str(tmp_path), "cap_1"))
    stats = cont.to_stats()
    assert stats["device_profile_windows_total"] == 1
    assert stats["device_profile_window_seconds_total"] == 0.25
    assert stats["device_profile_errors_total"] == 0
    assert stats["device_profile_duty_cycle"] <= 0.02


def test_a_started_sampler_is_armed_and_idles_until_its_first_window(tmp_path):
    """The default posture: the thread lives, no window is due for 30 s, nothing errs, and
    ``stop`` disarms it; a disabled configuration never starts."""
    cont = ContinuousProfiler(_StubProfiler(tmp_path), ContinuousProfileConfig())
    cont.start()
    try:
        assert cont.armed
        stats = cont.to_stats()
        assert stats["device_profile_windows_total"] == 0 and not cont.profiler.calls
        assert stats["device_profile_errors_total"] == 0
        assert stats["device_profile_duty_cycle"] <= 0.02
    finally:
        cont.stop()
    assert not cont.armed
    off = ContinuousProfiler(_StubProfiler(tmp_path), ContinuousProfileConfig(enabled=False))
    off.start()
    assert not off.armed


def test_sink_failure_does_not_kill_the_window(tmp_path):
    def bad_sink(_rec):
        raise RuntimeError("sink bug")

    cont, _ = _clocked(_StubProfiler(tmp_path), sink=bad_sink)
    assert cont.sample_once(force=True)["status"] == "ok"
    assert cont.windows_total == 1 and cont.errors_total == 0


# --- DeviceProfiler serialization under real thread races ---------------------
def test_capture_conflicts_serialize_not_overlap(tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    seq, started = [], threading.Event()
    lock = threading.Lock()

    def fake_start(path):
        with lock:
            seq.append("start")
        started.set()

    def fake_stop():
        with lock:
            seq.append("stop")

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake_stop)
    prof = DeviceProfiler(out_dir=str(tmp_path))

    results = {}
    t1 = threading.Thread(
        target=lambda: results.__setitem__("a", prof.capture(0.3, label="a")))
    t1.start()
    assert started.wait(5.0)
    # Non-waiting caller (the HTTP 409 path) gets a structured busy.
    busy = prof.capture(0.05, label="b", wait=False)
    assert busy["status"] == "busy" and busy["label"] == "b"
    # Waiting caller (incident path) queues behind the running window.
    t2 = threading.Thread(
        target=lambda: results.__setitem__("c", prof.capture(0.05, label="c",
                                                             wait=True)))
    assert prof.status()["busy"]
    t2.start()
    t1.join(10.0)
    t2.join(10.0)
    assert results["a"]["status"] == "ok" and results["c"]["status"] == "ok"
    st = prof.status()
    assert st["captures_total"] == 2
    assert st["capture_conflicts_total"] >= 1   # b for sure; c if it raced in
    assert not st["busy"]
    # THE invariant: trace windows never interleave.
    assert seq == ["start", "stop", "start", "stop"]


# --- measured truth in the flight recorder ------------------------------------
def _cost_model(**kw):
    kw.setdefault("param_count", 10**9)
    kw.setdefault("param_bytes", 2 * 10**9)
    kw.setdefault("kv_bytes_per_token", 1000.0)
    kw.setdefault("peak_flops", 1e14)
    kw.setdefault("peak_bw", 1e12)
    return StepCostModel(**kw)


def test_record_measured_window_derived_gauges():
    fr = FlightRecorder()
    fr.set_cost_model(_cost_model())
    assert "measured_windows_total" not in fr.to_stats()  # gated until data
    fr.record_measured_window({
        "wall_s": 0.25, "device_time_s": 0.2, "flops": 1e12, "bytes": 1e11,
        "step_seconds": 0.19, "top_kernel_share": 0.6,
        "top_kernels": [{"name": "ragged_paged_attention", "share": 0.6}],
    })
    stats = fr.to_stats()
    assert stats["measured_windows_total"] == 1
    assert stats["measured_mfu"] == pytest.approx(1e12 / 0.2 / 1e14)
    assert stats["measured_hbm_frac"] == pytest.approx(1e11 / 0.2 / 1e12)
    assert stats["measured_device_frac"] == pytest.approx(0.8)
    assert stats["measured_modeled_mfu_ratio"] == pytest.approx(0.19 / 0.2)
    assert stats["measured_top_kernel_share"] == pytest.approx(0.6)
    snap = fr.measured_snapshot()
    assert snap is not None and snap["top_kernels"][0]["name"] == "ragged_paged_attention"


def test_cost_model_calibration_band():
    cm = _cost_model()
    hand = 2.0 * cm.param_count
    assert cm.flops_per_token == hand and not cm.calibrated
    assert not cm.calibrate(hand * 0.1)       # below band: rejected
    assert not cm.calibrate(hand * 6.0)       # above band: rejected
    assert not cm.calibrate(0.0)
    assert cm.flops_per_token == hand and not cm.calibrated
    assert cm.calibrate(hand * 0.2)           # band edges inclusive
    assert cm.calibrated and cm.flops_per_token == hand * 0.2
    assert cm.calibration_source == "xla_cost_analysis"
    fr = FlightRecorder()
    fr.set_cost_model(cm)
    assert fr.to_stats()["cost_model_calibrated"] == 1.0


# --- profile-derived capacity -------------------------------------------------
def _measured_load(pre, dec, rate=4.0, isl=200.0, osl=50.0):
    return ObservedLoad(request_rate=rate, avg_isl=isl, avg_osl=osl,
                        measured_prefill_tok_s=pre, measured_decode_tok_s=dec)


def test_profiled_capacity_ema_and_gating():
    prior = StaticCapacityModel(400.0, 80.0, utilization=1.0)
    m = ProfiledCapacityModel(prior, alpha=0.5, min_windows=2)
    assert m.utilization == 1.0               # inherited from the prior
    m.observe(_measured_load(0.0, 0.0))       # idle window: never averaged in
    assert m.observations_total == 0
    m.observe(_measured_load(200.0, 40.0))    # first real window seeds the EMA
    assert m.measured_rates() == (0.0, 0.0)   # still riding the prior
    assert m.prefill_tokens_per_s(200.0) == 400.0
    m.observe(_measured_load(100.0, 20.0))
    assert m.measured_rates() == (150.0, 30.0)  # 200+0.5·(100−200), 40+0.5·(20−40)
    assert m.prefill_tokens_per_s(200.0) == 150.0
    assert m.decode_tokens_per_s(200.0, 50.0) == 30.0
    m.observe(_measured_load(0.0, 30.0))      # phases gate independently
    assert m.measured_rates() == (150.0, 30.0)
    assert m.observations_total == 3


def _view(pools):
    return FleetView(pools={
        PREFILL: [WorkerView(worker_id=100 + i) for i in range(pools[PREFILL])],
        DECODE: [WorkerView(worker_id=200 + i) for i in range(pools[DECODE])],
    }, drains_in_flight={})


def test_replay_decision_table_converges_to_measured_oracle():
    """The PR's closing loop: declared rates say 400/80 tok/s per worker,
    the device says 200/40. Replaying measured windows through decide(),
    the decision table starts at the declared-rate sizes and converges to
    the measured-rate oracle — then holds there."""
    prior = StaticCapacityModel(400.0, 80.0, utilization=1.0)
    model = ProfiledCapacityModel(prior, alpha=0.5, min_windows=2,
                                  utilization=1.0)
    ctrl = AutoscaleController(ControllerConfig(
        min_prefill=1, max_prefill=16, min_decode=1, max_decode=16,
        scale_cooldown_s=0.0, scale_up_stable_intervals=1,
        scale_down_stable_intervals=1, max_step=8, load_predictor="constant",
    ), model)
    pools = {PREFILL: 1, DECODE: 1}
    table = []
    now = 0.0
    for _ in range(6):
        decisions = ctrl.decide(_measured_load(200.0, 40.0), _view(pools), now)
        for d in decisions:
            if d.action != "hold":
                pools[d.pool] = d.target
        table.append((pools[PREFILL], pools[DECODE]))
        now += 30.0
    declared = prior.required(4.0, 200.0, 50.0)
    oracle = StaticCapacityModel(200.0, 40.0, utilization=1.0).required(
        4.0, 200.0, 50.0)
    assert table[0] == (declared[PREFILL], declared[DECODE]) == (2, 3)
    assert table[1] == (oracle[PREFILL], oracle[DECODE]) == (4, 5)
    assert table[-1] == table[-2] == table[-3] == (4, 5)  # converged, stable
    stats = ctrl.to_stats()
    assert stats["planner_measured_prefill_tok_s"] == 200.0
    assert stats["planner_measured_decode_tok_s"] == 40.0


def test_planner_stats_ride_prior_until_warm():
    ctrl = AutoscaleController(
        ControllerConfig(load_predictor="constant"),
        ProfiledCapacityModel(StaticCapacityModel(400.0, 80.0), min_windows=2))
    ctrl.decide(_measured_load(200.0, 40.0), _view({PREFILL: 1, DECODE: 1}), 0.0)
    stats = ctrl.to_stats()
    assert stats["planner_measured_prefill_tok_s"] == 0.0
    assert stats["planner_measured_decode_tok_s"] == 0.0
