"""Device-truth profiling: on-demand captures, a continuous sampler, and a
pure-stdlib trace-event parser.

Three layers, all exposed on the worker health server and the stats plane:

- ``DeviceProfiler`` — programmatic ``jax.profiler.start_trace`` /
  ``stop_trace`` capture windows. jax's profiler is process-global, so ALL
  capture paths (``POST /debug/profile``, incident-triggered captures, the
  continuous sampler) serialize through one capture lock; a caller that
  will not wait gets a structured "busy" answer and the collision is
  counted in ``capture_conflicts_total`` — never silently dropped.
- ``parse_trace_events`` / ``load_trace_dir`` — a pure-stdlib parser for
  the Chrome trace-event JSON jax writes next to the XPlane protos. It
  attributes device time per kernel name (count, total, max), computes the
  device-busy interval union per device lane, and tolerates truncated or
  malformed traces (a profiler window chopped by process exit must degrade
  to a partial summary, not a crash). Because it is plain ``json`` +
  ``zlib`` it runs on CPU CI against recorded fixtures.
- ``ContinuousProfiler`` — a duty-cycled background sampler that opens
  short capture windows at a bounded rate, parses the artifact, and feeds
  the per-window deltas (device time, kernel top-N) into the flight
  recorder so the modeled ``mfu_*`` / ``hbm_frac_*`` gauges gain *measured*
  siblings. The duty cycle is clamped
  (``window_s / effective_interval ≤ max_duty``) so the plane stays inside
  the observability budget, and the gating is pure arithmetic over an
  injected clock so CI can drive it deterministically.

- ``HostStackSampler`` — a pure-stdlib sampling profiler over
  ``sys._current_frames()`` attributing host time (the decode host gap) to
  actual scheduler code paths.

All of it is strictly off the hot path: captures run around a sleep
window on their own threads, parsing happens after the window closes, and
the observability bench runs with the continuous sampler ARMED to prove
the combination stays inside the ≤2% budget.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
import zlib
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from dynamo_tpu.runtime.logging import get_logger

logger = get_logger(__name__)

PROFILE_DIR_ENV = "DYN_PROFILE_DIR"

# /debug/profile refuses windows beyond this: a forgotten profiler is a
# disk- and overhead-leak on a production worker.
MAX_CAPTURE_SECONDS = 60.0


class DeviceProfiler:
    """Serialized programmatic jax.profiler captures.

    One capture at a time (jax's profiler is process-global). Concurrent
    callers pick their behavior: ``wait=False`` (the HTTP 409 path) gets a
    structured "busy" answer, ``wait=True`` (incident captures, which must
    not lose their window to a routine continuous sample) queues behind
    the running capture. Either way the collision increments
    ``capture_conflicts_total`` — a counter, not a silent drop. Capture
    errors (no backend, profiler unavailable) land in the result dict —
    a debug surface must degrade, not 500.
    """

    def __init__(self, out_dir: Optional[str] = None):
        self.out_dir = out_dir or os.environ.get(PROFILE_DIR_ENV) or "/tmp/dynamo_profiles"
        self._lock = threading.Lock()
        # Held for the whole trace window; THE serialization point for every
        # capture path (HTTP, incident, continuous).
        self._capture_lock = threading.Lock()
        self._busy = False  # guarded-by: _lock
        self._seq = 0  # guarded-by: _lock
        self.captures_total = 0  # guarded-by: _lock
        self.capture_conflicts_total = 0  # guarded-by: _lock
        self.last: Optional[dict] = None  # guarded-by: _lock

    def capture(self, seconds: float, label: str = "manual", wait: bool = False) -> dict:
        """Blocking capture: start the device trace, hold it open for
        ``seconds`` of live traffic, stop, return the artifact location.

        ``wait=False``: if another capture is running, return
        ``{"status": "busy"}`` immediately (and count the conflict).
        ``wait=True``: serialize behind the running capture instead.
        """
        seconds = min(max(float(seconds), 0.05), MAX_CAPTURE_SECONDS)
        if not self._capture_lock.acquire(blocking=False):
            with self._lock:
                self.capture_conflicts_total += 1
            if not wait:
                return {"status": "busy", "error": "a capture is already running",
                        "label": label}
            self._capture_lock.acquire()
        try:
            with self._lock:
                self._busy = True
                self._seq += 1
                seq = self._seq
            path = os.path.join(self.out_dir, f"profile_{seq:04d}_{label}")
            result = {"status": "ok", "path": path, "seconds": seconds, "label": label}
            try:
                import jax

                os.makedirs(path, exist_ok=True)
                jax.profiler.start_trace(path)
                try:
                    time.sleep(seconds)
                finally:
                    jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — degrade to a structured error
                result = {"status": f"error: {type(e).__name__}: {e}", "path": path,
                          "seconds": seconds, "label": label}
                logger.warning("device profile capture failed: %s", result["status"])
            with self._lock:
                self._busy = False
                if result["status"] == "ok":
                    self.captures_total += 1
                self.last = result
            return result
        finally:
            self._capture_lock.release()

    def capture_background(self, seconds: float, label: str = "incident") -> threading.Thread:
        """Fire-and-forget capture on a daemon thread (the incident-capture
        path: the stats scrape must not block on the profile window). Waits
        for a running capture rather than dropping the incident's window."""
        t = threading.Thread(
            target=self.capture, args=(seconds, label), kwargs={"wait": True},
            name="device-profile-capture", daemon=True,
        )
        t.start()
        return t

    def status(self) -> dict:
        with self._lock:
            return {
                "busy": self._busy,
                "captures_total": self.captures_total,
                "capture_conflicts_total": self.capture_conflicts_total,
                "out_dir": self.out_dir,
                "last": dict(self.last) if self.last else None,
            }


# ---------------------------------------------------------------------------
# Trace-event parsing (pure stdlib; runs on CPU CI against fixtures)
# ---------------------------------------------------------------------------

# Process-name patterns (lowercased substring match) that mark a trace lane
# as a device lane. jax/XProf names device processes "/device:TPU:0 ...";
# the fallback when no lane matches is to treat every duration event as a
# kernel (fixture traces and exotic backends still parse).
DEVICE_PROCESS_PATTERNS = ("/device:", "tpu", "gpu", "accelerator")

# Within a device process, kernels live on the "XLA Ops" thread; "XLA
# Modules"/"Steps" lanes hold enclosing spans that would double-count.
DEVICE_OPS_THREAD_PATTERNS = ("xla ops",)


@dataclass
class KernelStat:
    """Aggregate device time for one kernel name."""

    name: str
    count: int = 0
    total_us: float = 0.0
    max_us: float = 0.0

    def observe(self, dur_us: float) -> None:
        self.count += 1
        self.total_us += dur_us
        if dur_us > self.max_us:
            self.max_us = dur_us


@dataclass
class TraceSummary:
    """What one profile window measured, attributed per kernel."""

    kernels: Dict[str, KernelStat] = field(default_factory=dict)
    device_time_us: float = 0.0  # interval union of kernel events, per lane
    wall_us: float = 0.0  # span from first kernel start to last kernel end
    events_total: int = 0  # all ph=="X" events seen (host + device)
    kernel_events: int = 0  # ph=="X" events attributed to device lanes
    device_lanes: int = 0  # distinct (pid, tid) lanes kernels came from
    device_lane_found: bool = False  # False → fallback: every X event counted
    truncated: bool = False  # trace was cut; summary covers the prefix

    def top(self, n: int = 10) -> List[dict]:
        total = sum(k.total_us for k in self.kernels.values()) or 1.0
        ranked = sorted(self.kernels.values(), key=lambda k: -k.total_us)[:n]
        return [
            {"name": k.name, "count": k.count, "total_us": round(k.total_us, 3),
             "max_us": round(k.max_us, 3), "share": round(k.total_us / total, 4)}
            for k in ranked
        ]

    def launch_count(self, pattern: str) -> int:
        """Launches of kernels whose name contains ``pattern``."""
        return sum(k.count for name, k in self.kernels.items() if pattern in name)

    def top_share(self) -> float:
        total = sum(k.total_us for k in self.kernels.values())
        if total <= 0:
            return 0.0
        return max(k.total_us for k in self.kernels.values()) / total


def _union_us(intervals: List[Tuple[float, float]]) -> float:
    """Total covered length of a set of (start, end) intervals."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_s, cur_e = intervals[0]
    for s, e in intervals[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    total += cur_e - cur_s
    return total


def parse_trace_events(events: List[dict], *, truncated: bool = False) -> TraceSummary:
    """Attribute a Chrome trace-event list to per-kernel device time.

    Metadata events (``ph=="M"``) name the processes/threads; duration
    events (``ph=="X"``) on device lanes are kernels. When no lane looks
    like a device (CPU fixtures, unknown backends) every duration event is
    counted instead, so the parser degrades to "everything is a kernel"
    rather than an empty summary.
    """
    out = TraceSummary(truncated=truncated)
    process_names: Dict[object, str] = {}
    thread_names: Dict[Tuple[object, object], str] = {}
    durations: List[dict] = []
    for ev in events:
        if not isinstance(ev, dict):
            continue
        ph = ev.get("ph")
        if ph == "M":
            args = ev.get("args") or {}
            if ev.get("name") == "process_name":
                process_names[ev.get("pid")] = str(args.get("name", ""))
            elif ev.get("name") == "thread_name":
                thread_names[(ev.get("pid"), ev.get("tid"))] = str(args.get("name", ""))
        elif ph == "X":
            durations.append(ev)
    out.events_total = len(durations)

    device_pids = {
        pid for pid, name in process_names.items()
        if any(p in name.lower() for p in DEVICE_PROCESS_PATTERNS)
    }
    out.device_lane_found = bool(device_pids)

    def _is_kernel(ev: dict) -> bool:
        if not device_pids:
            return True  # fallback: no device lane — count everything
        pid = ev.get("pid")
        if pid not in device_pids:
            return False
        tname = thread_names.get((pid, ev.get("tid")), "").lower()
        # Only filter by thread when the device pid HAS named ops threads;
        # fixtures without thread metadata keep all device events.
        has_ops = any(
            any(p in tn.lower() for p in DEVICE_OPS_THREAD_PATTERNS)
            for (tpid, _), tn in thread_names.items() if tpid == pid
        )
        if not has_ops:
            return True
        return any(p in tname for p in DEVICE_OPS_THREAD_PATTERNS)

    lanes: Dict[Tuple[object, object], List[Tuple[float, float]]] = {}
    t0 = float("inf")
    t1 = float("-inf")
    for ev in durations:
        if not _is_kernel(ev):
            continue
        try:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur < 0:
            continue
        name = str(ev.get("name", "?"))
        stat = out.kernels.get(name)
        if stat is None:
            stat = out.kernels[name] = KernelStat(name)
        stat.observe(dur)
        out.kernel_events += 1
        lanes.setdefault((ev.get("pid"), ev.get("tid")), []).append((ts, ts + dur))
        t0 = min(t0, ts)
        t1 = max(t1, ts + dur)
    out.device_lanes = len(lanes)
    # Busy time is the per-lane interval union (nested/overlapping events in
    # one lane don't double-count) summed across lanes (parallel devices add).
    out.device_time_us = sum(_union_us(iv) for iv in lanes.values())
    out.wall_us = (t1 - t0) if out.kernel_events else 0.0
    return out


def _decompress_partial(data: bytes) -> bytes:
    """Gunzip as much as survives — a truncated .gz yields its prefix."""
    d = zlib.decompressobj(16 + zlib.MAX_WBITS)
    out = []
    for i in range(0, len(data), 1 << 16):
        try:
            out.append(d.decompress(data[i:i + (1 << 16)]))
        except zlib.error:
            break
    return b"".join(out)


def _scan_events(text: str) -> Tuple[List[dict], bool]:
    """Extract the traceEvents list, tolerating truncation: when the full
    document fails to parse, raw_decode individual events until the cut."""
    try:
        obj = json.loads(text)
        if isinstance(obj, dict):
            events = obj.get("traceEvents", [])
        elif isinstance(obj, list):
            events = obj
        else:
            events = []
        return [e for e in events if isinstance(e, dict)], False
    except ValueError:
        pass
    idx = text.find('"traceEvents"')
    start = text.find("[", idx if idx >= 0 else 0)
    if start < 0:
        return [], True
    dec = json.JSONDecoder()
    events: List[dict] = []
    i = start + 1
    n = len(text)
    while True:
        while i < n and text[i] in " \t\r\n,":
            i += 1
        if i >= n or text[i] == "]":
            break
        try:
            ev, i = dec.raw_decode(text, i)
        except ValueError:
            break  # the cut point — keep what we recovered
        if isinstance(ev, dict):
            events.append(ev)
    return events, True


def parse_trace_bytes(data: bytes) -> TraceSummary:
    """Parse raw trace-event bytes (gzipped or plain, possibly truncated)."""
    if data[:2] == b"\x1f\x8b":
        data = _decompress_partial(data)
    text = data.decode("utf-8", "replace")
    events, truncated = _scan_events(text)
    return parse_trace_events(events, truncated=truncated)


def load_trace_dir(path: str) -> Optional[TraceSummary]:
    """Find and parse the newest ``*.trace.json[.gz]`` under a capture
    directory (jax writes ``plugins/profile/<run>/<host>.trace.json.gz``).
    Returns None when no trace artifact exists."""
    newest: Optional[str] = None
    newest_mtime = -1.0
    try:
        for root, _dirs, files in os.walk(path):
            for fn in files:
                if fn.endswith(".trace.json.gz") or fn.endswith(".trace.json"):
                    p = os.path.join(root, fn)
                    try:
                        m = os.path.getmtime(p)
                    except OSError:
                        continue
                    if m > newest_mtime:
                        newest, newest_mtime = p, m
    except OSError:
        return None
    if newest is None:
        return None
    try:
        with open(newest, "rb") as f:
            return parse_trace_bytes(f.read())
    except OSError:
        return None


# ---------------------------------------------------------------------------
# Continuous sampling
# ---------------------------------------------------------------------------


@dataclass
class ContinuousProfileConfig:
    """Knobs for the background device-truth sampler.

    Defaults are production-safe: a 250ms window every 30s is a 0.83%
    profiling duty cycle, further clamped by ``max_duty`` — the effective
    interval is ``max(interval_s, window_s / max_duty)``.
    """

    enabled: bool = True
    window_s: float = 0.25
    interval_s: float = 30.0
    max_duty: float = 0.02
    keep_artifacts: bool = False
    top_n: int = 8


class ContinuousProfiler:
    """Duty-cycled background device captures feeding measured truth into
    the flight recorder.

    ``cost_probe`` returns the flight recorder's cumulative
    ``(flops, bytes, step_seconds)`` so each window's deltas
    attribute measured device time to modeled work done in the same span;
    ``sink`` receives the per-window record (normally
    ``FlightRecorder.record_measured_window``). The sampler always YIELDS
    to on-demand/incident captures: a busy profiler means the window is
    skipped and counted, never queued behind debug traffic.
    """

    def __init__(
        self,
        profiler: DeviceProfiler,
        config: Optional[ContinuousProfileConfig] = None,
        *,
        cost_probe: Optional[Callable[[], Tuple[float, float, float]]] = None,
        sink: Optional[Callable[[dict], None]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.profiler = profiler
        self.config = config or ContinuousProfileConfig()
        self.cost_probe = cost_probe
        self.sink = sink
        self.clock = clock
        self._lock = threading.Lock()
        self._last_attempt = clock()  # guarded-by: _lock — first window waits a full interval
        self.windows_total = 0  # guarded-by: _lock
        self.window_seconds_total = 0.0  # guarded-by: _lock
        self.skipped_busy_total = 0  # guarded-by: _lock
        self.errors_total = 0  # guarded-by: _lock
        self.last: Optional[dict] = None  # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # --- pure gating (unit-testable under an injected clock) ---------------
    @property
    def effective_interval_s(self) -> float:
        floor = self.config.window_s / max(self.config.max_duty, 1e-6)
        return max(self.config.interval_s, floor)

    @property
    def duty_cycle(self) -> float:
        return self.config.window_s / self.effective_interval_s

    def due(self, now: float) -> bool:
        with self._lock:
            return (now - self._last_attempt) >= self.effective_interval_s

    # --- one window ---------------------------------------------------------
    def sample_once(self, now: Optional[float] = None, force: bool = False) -> dict:
        """Open one capture window if the rate limiter allows, parse the
        artifact, and push the measured record to the sink."""
        if now is None:
            now = self.clock()
        with self._lock:
            if not force and (now - self._last_attempt) < self.effective_interval_s:
                return {"status": "not_due"}
            self._last_attempt = now
        pre = self.cost_probe() if self.cost_probe else (0.0, 0.0, 0.0)
        res = self.profiler.capture(self.config.window_s, label="continuous", wait=False)
        status = res.get("status")
        if status == "busy":
            with self._lock:
                self.skipped_busy_total += 1
            return {"status": "skipped_busy"}
        if status != "ok":
            with self._lock:
                self.errors_total += 1
            return res
        post = self.cost_probe() if self.cost_probe else (0.0, 0.0, 0.0)
        summary = load_trace_dir(res["path"])
        if not self.config.keep_artifacts:
            shutil.rmtree(res["path"], ignore_errors=True)
        if summary is None:
            with self._lock:
                self.errors_total += 1
            return {"status": "error: no trace artifact", "path": res["path"]}
        record = {
            "status": "ok",
            "wall_s": self.config.window_s,
            "device_time_s": summary.device_time_us / 1e6,
            "flops": max(0.0, post[0] - pre[0]),
            "bytes": max(0.0, post[1] - pre[1]),
            "step_seconds": max(0.0, post[2] - pre[2]),
            "kernel_events": summary.kernel_events,
            "device_lanes": summary.device_lanes,
            "device_lane_found": summary.device_lane_found,
            "truncated": summary.truncated,
            "top_kernels": summary.top(self.config.top_n),
            "top_kernel_share": summary.top_share(),
        }
        with self._lock:
            self.windows_total += 1
            self.window_seconds_total += self.config.window_s
            self.last = record
        if self.sink is not None:
            try:
                self.sink(record)
            except Exception as e:  # noqa: BLE001 — a sink bug must not kill the sampler
                logger.warning("measured-window sink failed: %s", e)
        return record

    # --- background thread --------------------------------------------------
    def start(self) -> None:
        if not self.config.enabled:
            return
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="continuous-profiler", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    @property
    def armed(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        poll = min(5.0, max(0.25, self.effective_interval_s / 20.0))
        while not self._stop.wait(poll):
            try:
                self.sample_once()
            except Exception as e:  # noqa: BLE001 — the sampler must outlive one bad window
                with self._lock:
                    self.errors_total += 1
                logger.warning("continuous profile window failed: %s", e)

    def to_stats(self) -> dict:
        """Wire-format stats families (pure dict assembly, no device work)."""
        with self._lock:
            return {
                "device_profile_windows_total": self.windows_total,
                "device_profile_window_seconds_total": self.window_seconds_total,
                "device_profile_skipped_busy_total": self.skipped_busy_total,
                "device_profile_errors_total": self.errors_total,
                "device_profile_duty_cycle": self.duty_cycle,
            }


def _frame_key(frame) -> Optional[str]:
    """Innermost frame inside this package, as ``file:line func`` — the
    attribution unit. Frames entirely outside dynamo_tpu (idle selector
    loops, queue waits in aiohttp) collapse to their leaf frame."""
    f = frame
    while f is not None:
        fn = f.f_code.co_filename
        if "dynamo_tpu" in fn:
            short = fn[fn.rindex("dynamo_tpu"):]
            return f"{short}:{f.f_lineno} {f.f_code.co_name}"
        f = f.f_back
    return None


class HostStackSampler:
    """Stdlib sampling profiler: attributes host time to code paths.

    ``start()``/``stop()`` run it continuously from a daemon thread;
    ``sample_for(seconds)`` is the blocking one-shot used by
    ``POST /debug/profile?kind=host``. ``report()`` returns the top frames
    overall plus the ``engine/scheduler.py`` rollup — the "which scheduler
    code path owns the host gap" answer.
    """

    def __init__(self, interval_s: float = 0.005):
        self.interval_s = max(float(interval_s), 0.001)
        self._lock = threading.Lock()
        self._counts: Counter = Counter()  # guarded-by: _lock
        self._other = 0  # guarded-by: _lock  (samples with no dynamo frame)
        self.samples = 0  # guarded-by: _lock
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # --- continuous mode ----------------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="host-stack-sampler", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._sample()

    # --- one-shot mode ------------------------------------------------------
    def sample_for(self, seconds: float) -> dict:
        """Blocking burst of samples for ``seconds``; returns the report of
        ONLY this burst (state is reset first)."""
        self.reset()
        deadline = time.monotonic() + min(max(float(seconds), 0.05), MAX_CAPTURE_SECONDS)
        while time.monotonic() < deadline:
            self._sample()
            time.sleep(self.interval_s)
        return self.report()

    # --- core ---------------------------------------------------------------
    def _sample(self) -> None:
        me = threading.get_ident()
        hits: List[str] = []
        misses = 0
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            key = _frame_key(frame)
            if key is None:
                misses += 1
            else:
                hits.append(key)
        with self._lock:
            self.samples += 1
            self._other += misses
            for key in hits:
                self._counts[key] += 1

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._other = 0
            self.samples = 0

    def report(self, top: int = 15) -> dict:
        """Top frames by samples + the scheduler-path rollup share."""
        with self._lock:
            counts = Counter(self._counts)
            samples = self.samples
            other = self._other
        total_hits = sum(counts.values())
        sched = sum(c for k, c in counts.items() if "engine/scheduler.py" in k)
        return {
            "samples": samples,
            "attributed": total_hits,
            "unattributed_thread_samples": other,
            "scheduler_share": round(sched / total_hits, 4) if total_hits else 0.0,
            "top": [
                {
                    "frame": key,
                    "count": c,
                    "share": round(c / total_hits, 4) if total_hits else 0.0,
                }
                for key, c in counts.most_common(top)
            ],
        }
