"""Engine flight recorder: step histograms + XLA compile tracking.

The scheduler's step loop is where serving latency is actually spent, but
until now its only outputs were aggregate counters. The flight recorder
keeps a host-side, allocation-free account of every dispatch:

- **Step-duration histograms labelled by phase** (prefill / decode / mixed /
  wave / spec) with per-phase token counts — the per-step token throughput
  and the "where did this request's time go" denominator.
- **An XLA compile tracker.** Executables are keyed by their static shape
  tuple (the same keys ``Scheduler.warmup`` precompiles). Every dispatch
  registers its key; a key first seen *after* warmup completed means XLA
  compiled mid-traffic — PR 1's silent killer (decode executables compiling
  under load, measured as the dominant serving-plane latency) — and is
  counted and logged with its shape key so it alerts instead of hiding in
  p99. A key first seen is a guess that XLA compiled: what JAX really built,
  with its key and its seconds, is the build log's (``self.builds``,
  engine/compile_cache.py).

Everything is plain Python ints/floats mutated from the step thread and
read from the event loop via ``to_stats()`` — last-write-wins races on a
scrape are acceptable for monitoring data, so no locks on the hot path.
"""

from __future__ import annotations

import bisect
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

from dynamo_tpu.engine.compile_cache import BUILD_LOG
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.tracing import StepLog

logger = get_logger(__name__)

# Step durations span sub-ms CPU mock steps to multi-second cold compiles.
STEP_BUCKETS: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0
)

PHASES = ("prefill", "decode", "mixed", "wave", "spec")

# Name of the step-log entry ``record_step`` writes: one per dispatch, its
# interval the dispatch's timed part, ``attrs`` its phase and tokens — the
# /debug/state and incident-bundle ``recent_steps`` timeline is read from it.
STEP_RECORD = "flight.step"
RECENT_STEPS = 64

# Host-gap buckets: the window between a decode dispatch returning and the
# next one being issued (readback + bookkeeping + upload) — far
# finer-grained than step durations.
GAP_BUCKETS: Tuple[float, ...] = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25
)


class _PhaseHist:
    __slots__ = ("counts", "total", "sum_s", "tokens", "buckets")

    def __init__(self, buckets: Tuple[float, ...] = STEP_BUCKETS) -> None:
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.total = 0
        self.sum_s = 0.0
        self.tokens = 0

    def observe(self, dur_s: float, tokens: int) -> None:
        self.counts[bisect.bisect_left(self.buckets, dur_s)] += 1
        self.total += 1
        self.sum_s += dur_s
        self.tokens += tokens

    def percentile(self, q: float) -> float:
        """Approximate q-quantile (0..1) from the bucket counts: linear
        interpolation within the covering bucket, upper bound for +Inf."""
        if not self.total:
            return 0.0
        rank = q * self.total
        seen = 0
        lo = 0.0
        for i, c in enumerate(self.counts):
            hi = self.buckets[i] if i < len(self.buckets) else self.buckets[-1]
            if seen + c >= rank:
                if c == 0:
                    return hi
                frac = (rank - seen) / c
                return lo + frac * (hi - lo)
            seen += c
            lo = hi
        return self.buckets[-1]


# Peak hardware numbers for the live MFU / HBM-roofline gauges, keyed by the
# ``device_kind`` JAX reports (both spellings jax's own tpu_info accepts).
# Source: Google Cloud TPU documentation, per-chip bf16 FLOP/s and HBM
# bytes/s. An accelerator that is not in the table is an error, not a
# default. The CPU keeps a nominal value only so that tests run: the gauges'
# absolute value is meaningless off-accelerator.
CHIP_PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}
_CPU_PEAKS = (1e12, 100e9)


def peaks_for(platform: str, device_kind: str) -> Tuple[float, float]:
    """(peak FLOPs/s, peak HBM bytes/s) of one device as JAX reports it."""
    if platform == "cpu":
        return _CPU_PEAKS
    if device_kind not in CHIP_PEAKS:
        raise ValueError(
            f"no peak FLOP/s and HBM bytes/s on record for accelerator "
            f"{device_kind!r} (platform {platform!r}): add its row to CHIP_PEAKS"
        )
    return CHIP_PEAKS[device_kind]


def detect_peaks() -> Tuple[float, float]:
    """(peak FLOPs/s, peak HBM bytes/s) for the local device."""
    import jax

    dev = jax.devices()[0]
    return peaks_for(dev.platform, dev.device_kind)


class StepCostModel:
    """Per-step FLOPs + bytes model so BENCH roofline numbers become a live
    metric. Analytical, host-side only:

    - FLOPs ≈ 2 · params · tokens (the matmul-dominated transformer count;
      attention FLOPs are second-order at serving context lengths).
    - Bytes: decode/mixed steps stream the whole parameter set once per
      parameter pass plus the active KV they read; prefill writes its
      chunk's KV and re-reads the prefix.

    ``param_count``/``param_bytes`` come from the actual params pytree and
    ``kv_bytes_per_token`` from the actual cache arrays, so quantized
    deployments (int8 weights/KV) are modeled at their real byte widths.

    ``kv_read_factor`` models the attention path's traffic amplification
    over the true prefix bytes: the XLA width-bucketed gather materializes
    a packed copy (gather read + copy write + attend re-read ⇒ 3.0), while
    the paged Pallas paths — the opt-in r5 kernel and the ragged
    megakernel — stream each page HBM→VMEM exactly once (1.0). With the
    factor wrong the live ``hbm_frac_decode`` gauge would report the
    megakernel at a third of its real roofline fraction (or the gather at
    3× — either way, not the number BENCH_r* anchors).
    """

    __slots__ = ("param_count", "param_bytes", "kv_bytes_per_token",
                 "kv_read_factor", "peak_flops", "peak_bw",
                 "flops_per_token", "calibrated", "calibration_source")

    # XLA's own count must land within this band of the 2·params hand count
    # to be trusted: a wildly different number means the probe measured the
    # wrong executable (or cost_analysis returned transcendental-op noise),
    # and silently adopting it would skew every mfu_* gauge and the
    # measured-vs-modeled tolerance gate downstream.
    CALIBRATION_BAND = (0.2, 5.0)

    def __init__(self, param_count: int, param_bytes: int, kv_bytes_per_token: float,
                 peak_flops: Optional[float] = None, peak_bw: Optional[float] = None,
                 kv_read_factor: float = 1.0):
        self.param_count = max(int(param_count), 1)
        self.param_bytes = max(int(param_bytes), 1)
        self.kv_bytes_per_token = max(float(kv_bytes_per_token), 0.0)
        self.kv_read_factor = max(float(kv_read_factor), 0.0)
        if peak_flops is None or peak_bw is None:
            peak_flops, peak_bw = detect_peaks()
        self.peak_flops = peak_flops
        self.peak_bw = peak_bw
        # Hand-rolled default; Scheduler warmup replaces it with XLA's own
        # cost_analysis() count of the decode executable when available.
        self.flops_per_token = 2.0 * self.param_count
        self.calibrated = False
        self.calibration_source = "analytical"

    def calibrate(self, flops_per_token: float, source: str = "xla_cost_analysis") -> bool:
        """Adopt a measured FLOPs-per-token count (normally from
        ``jax.stages.Compiled.cost_analysis()``). Rejected outside the
        sanity band around the analytical count — returns whether adopted."""
        hand = 2.0 * self.param_count
        lo, hi = self.CALIBRATION_BAND
        if not (flops_per_token > 0 and lo * hand <= flops_per_token <= hi * hand):
            logger.warning(
                "rejecting cost_analysis calibration %.3g flops/token "
                "(analytical %.3g, accepted band [%.1fx, %.1fx])",
                flops_per_token, hand, lo, hi,
            )
            return False
        self.flops_per_token = float(flops_per_token)
        self.calibrated = True
        self.calibration_source = source
        return True

    def step_cost(
        self, tokens: int, kv_read_tokens: int, param_passes: float = 1.0
    ) -> Tuple[float, float]:
        """(flops, bytes) for one dispatch computing ``tokens`` token rows
        while reading ``kv_read_tokens`` of resident KV.

        ``param_passes``: how many times the dispatch streams the parameter
        set from HBM — 1 for single steps, ``num_steps`` for a
        ``decode_multi`` window (the fori_loop re-reads weights every
        step)."""
        flops = self.flops_per_token * tokens
        bytes_moved = (
            # (0 passes: a program that reads no weights, as a roll of KV rows)
            self.param_bytes * (max(param_passes, 1.0) if param_passes else 0.0)
            + kv_read_tokens * self.kv_bytes_per_token * self.kv_read_factor
            + tokens * self.kv_bytes_per_token  # written KV rows
        )
        return flops, bytes_moved

    def roofline_time(self, flops: float, bytes_moved: float) -> float:
        """Lower-bound seconds for (flops, bytes) on this chip — the
        max(compute, bandwidth) roofline. Used to split a mixed step's
        wall time between its phases."""
        return max(flops / self.peak_flops, bytes_moved / self.peak_bw)


class _PhaseRoofline:
    """Rolling (flops, bytes, seconds) account per phase: the live-gauge
    window. A bounded deque of recent steps, so a quiet engine's MFU decays
    to reflect recent traffic rather than all-time averages."""

    __slots__ = ("recent", "flops_total", "bytes_total", "secs_total")

    def __init__(self, maxlen: int = 256):
        self.recent: deque = deque(maxlen=maxlen)  # (flops, bytes, dur_s)
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.secs_total = 0.0

    def record(self, flops: float, bytes_moved: float, dur_s: float) -> None:
        self.recent.append((flops, bytes_moved, dur_s))
        self.flops_total += flops
        self.bytes_total += bytes_moved
        self.secs_total += dur_s

    def live(self, peak_flops: float, peak_bw: float) -> Tuple[float, float]:
        """(MFU, HBM-roofline fraction) over the recent-step window."""
        if not self.recent:
            return 0.0, 0.0
        f = sum(x for x, _, _ in self.recent)
        b = sum(x for _, x, _ in self.recent)
        t = sum(x for _, _, x in self.recent)
        if t <= 0:
            return 0.0, 0.0
        return f / t / peak_flops, b / t / peak_bw


class FlightRecorder:
    """Owned by one Scheduler; mutated on the step thread only."""

    def __init__(self, telemetry=None, log: Optional[StepLog] = None) -> None:
        self._hists: Dict[str, _PhaseHist] = {p: _PhaseHist() for p in PHASES}
        # Optional runtime.telemetry.Telemetry: record_step feeds per-phase
        # ``{phase}_step`` digests so step-duration percentiles merge
        # fleet-wide (the bucket histograms above stay for bench readers).
        self.telemetry = telemetry
        # Per-step FLOPs+bytes roofline account (set_cost_model); None keeps
        # record_step cost-free for schedulers that never attach one.
        self.cost_model: Optional[StepCostModel] = None
        self._roofline: Dict[str, _PhaseRoofline] = {}
        # The engine's step log (runtime/tracing.py): every span of the
        # served path and every finished request, and — through record_step's
        # own entries — the /debug/state step timeline. ``TpuEngine.build``
        # hands over the log its ``engine.build`` span opened on.
        self.log = log if log is not None else StepLog()
        # What JAX really built (engine/compile_cache.py: the process's log,
        # fed by JAX's own events), read from ``since_ns`` on: this recorder's
        # creation, or the start of the ``engine.build`` that made it.
        self.builds = BUILD_LOG
        self.since_ns = time.monotonic_ns()
        # Stall watchdog reference point.
        self.last_step_ts: Optional[float] = None
        # Decode host gap: time from a decode dispatch RETURNING (device
        # launched, host free) to the NEXT decode dispatch being issued —
        # the bubble the device spends waiting on Python. Only consecutive
        # decode-family dispatches are measured (phase changes reset it).
        self._gap = _PhaseHist(GAP_BUCKETS)
        # Compile tracker state.
        self._exec_keys: Set[tuple] = set()
        self.last_exec: Optional[tuple] = None  # the newest (kind, *key): a launch's scope in the build log
        self.compiles_total = 0
        self.compiles_after_warmup_total = 0
        self.post_warmup_keys: List[tuple] = []
        self._warmup_done = False
        self._warmed = False  # did a real warmup() pass run before traffic?
        # Last-step snapshot (gauge-style, for quick introspection).
        self.last_step_phase: Optional[str] = None
        self.last_step_s = 0.0
        # Measured device truth (ContinuousProfiler windows). Written from
        # the profiler thread — distinct fields with a single writer, read
        # by the scrape; last-write-wins is fine for monitoring data.
        self.measured_windows_total = 0
        self.measured_device_seconds_total = 0.0
        self.measured_wall_seconds_total = 0.0
        self._measured_last: Optional[dict] = None

    # --- measured device truth ----------------------------------------------
    def roofline_totals(self) -> Tuple[float, float, float]:
        """Cumulative (flops, bytes, modeled step seconds) across every
        phase — the ContinuousProfiler's cost probe. Deltas of this across a
        profile window attribute measured device time to the modeled work
        done in the same span."""
        f = b = s = 0.0
        for r in self._roofline.values():
            f += r.flops_total
            b += r.bytes_total
            s += r.secs_total
        return f, b, s

    def record_measured_window(self, record: dict) -> None:
        """Fold one profile window's measured truth into the recorder.

        ``record`` is the ContinuousProfiler's per-window dict (or a bench
        fixture shaped the same): wall_s, device_time_s, flops, bytes,
        step_seconds, top_kernels, top_kernel_share. Derived gauges:

        - ``measured_mfu`` / ``measured_hbm_frac``: modeled work ÷ MEASURED
          device-busy time ÷ peak — the measured sibling of ``mfu_*``.
        - ``measured_modeled_mfu_ratio``: modeled step seconds ÷ measured
          device seconds over the same span. 1.0 means the cost model's
          wall clock and the device's own account agree; the bench asserts
          a tolerance band on the fixture path.
        """
        device_s = max(float(record.get("device_time_s", 0.0)), 0.0)
        flops = max(float(record.get("flops", 0.0)), 0.0)
        bytes_moved = max(float(record.get("bytes", 0.0)), 0.0)
        step_s = max(float(record.get("step_seconds", 0.0)), 0.0)
        self.measured_windows_total += 1
        self.measured_device_seconds_total += device_s
        self.measured_wall_seconds_total += float(record.get("wall_s", 0.0))
        mfu = hbm = 0.0
        if self.cost_model is not None and device_s > 0:
            mfu = flops / device_s / self.cost_model.peak_flops
            hbm = bytes_moved / device_s / self.cost_model.peak_bw
        ratio = (step_s / device_s) if device_s > 0 else 0.0
        self._measured_last = {
            "measured_mfu": round(mfu, 6),
            "measured_hbm_frac": round(hbm, 6),
            "measured_device_frac": (
                round(device_s / float(record["wall_s"]), 6)
                if record.get("wall_s") else 0.0
            ),
            "measured_modeled_mfu_ratio": round(ratio, 6),
            "measured_top_kernel_share": round(
                float(record.get("top_kernel_share", 0.0)), 6
            ),
            "top_kernels": record.get("top_kernels", []),
        }

    def measured_snapshot(self) -> Optional[dict]:
        """Last measured window's derived gauges + kernel top-N (bench and
        incident-bundle view); None before the first window."""
        last = self._measured_last
        return dict(last) if last else None

    # --- step accounting ----------------------------------------------------
    def set_cost_model(self, model: StepCostModel) -> None:
        """Attach the per-step FLOPs+bytes model: record_step then keeps a
        live per-phase MFU / HBM-roofline account."""
        self.cost_model = model

    def record_step(
        self, phase: str, dur_s: float, tokens: int, kv_read_tokens: int = 0,
        param_passes: float = 1.0,
    ) -> None:
        h = self._hists.get(phase)
        if h is None:
            h = self._hists.setdefault(phase, _PhaseHist())
        h.observe(dur_s, tokens)
        self._log_step(phase, dur_s, tokens)
        if self.telemetry is not None:
            self.telemetry.observe(f"{phase}_step", dur_s)
        if self.cost_model is not None:
            flops, bytes_moved = self.cost_model.step_cost(
                tokens, kv_read_tokens, param_passes
            )
            self._record_roofline(phase, flops, bytes_moved, dur_s)

    def _log_step(self, phase: str, dur_s: float, tokens: int) -> None:
        now = time.monotonic_ns()
        self.last_step_phase = phase
        self.last_step_s = dur_s
        self.last_step_ts = now / 1e9  # time.monotonic()'s clock
        self.log.spans.append(
            (STEP_RECORD, now - int(dur_s * 1e9), now, self.log.step, {"phase": phase, "tokens": tokens})
        )

    def recent_steps(self, n: int = RECENT_STEPS) -> List[dict]:
        """The newest ``n`` dispatches, oldest first, in the shape
        /debug/state, incident bundles and ``tools/autopsy.py`` read. A
        dispatch that carried a prefill chunk also says how the chunk met
        its keys (``chunk_attn``, from its iteration's ``sched.step``)."""
        now = time.monotonic_ns()
        paths = {step: a["chunk_attn"] for _, _, _, step, a in self.log.named("sched.step") if a and "chunk_attn" in a}
        return [
            {"age_s": round((now - t1) / 1e9, 3), "phase": a["phase"],
             "dur_s": round((t1 - t0) / 1e9, 6), "tokens": a["tokens"],
             **({"chunk_attn": paths[step]} if step in paths and a["phase"] in ("prefill", "mixed") else {})}
            for _, t0, t1, step, a in self.log.named(STEP_RECORD, n)
        ]

    def _record_roofline(
        self, phase: str, flops: float, bytes_moved: float, dur_s: float
    ) -> None:
        r = self._roofline.get(phase)
        if r is None:
            r = self._roofline.setdefault(phase, _PhaseRoofline())
        r.record(flops, bytes_moved, dur_s)

    def record_mixed_step(
        self,
        dur_s: float,
        prefill_tokens: int,
        decode_tokens: int,
        kv_read_prefill: int = 0,
        kv_read_decode: int = 0,
    ) -> None:
        """One MIXED prefill+decode dispatch. The step histogram stays under
        the "mixed" phase (steps/time/tokens counters unchanged), but the
        FLOPs/bytes roofline account is SPLIT into the prefill and decode
        buckets: when the fused kernel serves both phases in one launch,
        charging everything to "mixed" would starve ``mfu_prefill`` and
        ``hbm_frac_decode`` of exactly the traffic mixed steps carry —
        under heavy mixed batching those gauges would decay to zero while
        the engine is at peak. Wall time is apportioned by each phase's
        roofline-time share (prefill chunks are FLOPs-bound, decode rows
        bytes-bound, so a 50/50 token split is NOT a 50/50 time split)."""
        h = self._hists["mixed"]
        h.observe(dur_s, prefill_tokens + decode_tokens)
        self._log_step("mixed", dur_s, prefill_tokens + decode_tokens)
        if self.telemetry is not None:
            self.telemetry.observe("mixed_step", dur_s)
        if self.cost_model is None:
            return
        # The parameter stream is shared by both phases in one dispatch —
        # attribute it to the decode rows (a mixed step exists because the
        # decode batch was running anyway; the chunk rides for free).
        f_p, b_p = self.cost_model.step_cost(prefill_tokens, kv_read_prefill, 0.0)
        f_d, b_d = self.cost_model.step_cost(decode_tokens, kv_read_decode, 1.0)
        t_p = self.cost_model.roofline_time(f_p, b_p)
        t_d = self.cost_model.roofline_time(f_d, b_d)
        share_p = t_p / (t_p + t_d) if (t_p + t_d) > 0 else 0.5
        if prefill_tokens > 0:
            self._record_roofline("prefill", f_p, b_p, dur_s * share_p)
        if decode_tokens > 0:
            self._record_roofline("decode", f_d, b_d, dur_s * (1.0 - share_p))

    def utilization(self) -> Dict[str, Tuple[float, float]]:
        """{phase: (mfu, hbm_roofline_fraction)} over the recent-step
        window; empty without a cost model."""
        if self.cost_model is None:
            return {}
        return {
            phase: r.live(self.cost_model.peak_flops, self.cost_model.peak_bw)
            for phase, r in self._roofline.items()
        }

    def record_host_gap(self, gap_s: float) -> None:
        """One dispatch-return → next-dispatch interval on the decode path."""
        self._gap.observe(gap_s, 0)

    def gap_percentile(self, q: float) -> float:
        """Approximate decode-host-gap quantile in SECONDS (bench reporting)."""
        return self._gap.percentile(q)

    # --- compile tracking ---------------------------------------------------
    def record_exec(self, kind: str, key: tuple) -> bool:
        """Register a dispatch's executable shape key. Returns True when the
        key is new (a guess that XLA compiled for it: what JAX really built
        is in ``self.builds``). New keys after warmup are the alert condition."""
        k = self.last_exec = (kind,) + tuple(key)
        if k in self._exec_keys:
            return False
        self._exec_keys.add(k)
        self.compiles_total += 1
        if self._warmup_done:
            self.compiles_after_warmup_total += 1
            self.post_warmup_keys.append(k)
            # A warmed engine compiling mid-traffic is a coverage bug worth
            # alerting on; an engine that skipped warmup compiles lazily by
            # design — record it, but don't cry wolf.
            log = logger.warning if self._warmed else logger.debug
            log("XLA compile after warmup: %s %s (post-warmup compiles: %d)",
                kind, key, self.compiles_after_warmup_total)
        return True

    def mark_warmup_done(self, warmed: bool) -> None:
        """Called once traffic may start. ``warmed`` = a warmup() pass
        actually precompiled the serving set (compiles after this point are
        unexpected); False = lazy compilation is expected but still
        counted."""
        self._warmup_done = True
        self._warmed = warmed
        self.builds.serving = True  # entries outside every scope carry phase "serving" from here on

    def exec_key_summary(self) -> Dict[str, List[int]]:
        """{kind: sorted key arities} of every executable key registered so
        far — the dynamic twin of dtlint's ``static_warmup_report()``.
        ``tests/test_decode_paths.py`` holds a warmed scheduler's keys
        inside the static enumeration, so the two cannot drift apart."""
        out: Dict[str, Set[int]] = {}
        for k in self._exec_keys:
            out.setdefault(k[0], set()).add(len(k) - 1)
        return {kind: sorted(v) for kind, v in sorted(out.items())}

    # --- export -------------------------------------------------------------
    def to_stats(self) -> dict:
        """Flat dict merged into the worker stats scrape (monotonic keys end
        in ``_total`` so the aggregator exports them as Counters)."""
        out: dict = {
            "compiles_total": self.compiles_total,
            "compiles_after_warmup_total": self.compiles_after_warmup_total,
            # Host-gap histogram exported as sum+count counters: PromQL
            # rate(sum)/rate(count) is the live average gap; bench reads
            # the full bucket histogram host-side for p50/p99.
            "decode_host_gap_events_total": self._gap.total,
            "decode_host_gap_seconds_total": round(self._gap.sum_s, 6),
        }
        for phase, h in self._hists.items():
            if not h.total and phase not in ("prefill", "decode", "mixed"):
                continue  # wave/spec only when the path is exercised
            out[f"step_{phase}_steps_total"] = h.total
            out[f"step_{phase}_time_seconds_total"] = round(h.sum_s, 6)
            out[f"step_{phase}_tokens_total"] = h.tokens
        if self.cost_model is not None:
            for phase, r in self._roofline.items():
                out[f"step_{phase}_flops_total"] = round(r.flops_total, 1)
                out[f"step_{phase}_bytes_total"] = round(r.bytes_total, 1)
                mfu, hbm = r.live(self.cost_model.peak_flops, self.cost_model.peak_bw)
                out[f"mfu_{phase}"] = round(mfu, 6)
                out[f"hbm_frac_{phase}"] = round(hbm, 6)
            out["cost_model_calibrated"] = 1.0 if self.cost_model.calibrated else 0.0
        if self.measured_windows_total:
            out["measured_windows_total"] = self.measured_windows_total
            out["measured_device_seconds_total"] = round(
                self.measured_device_seconds_total, 6
            )
            out["measured_wall_seconds_total"] = round(
                self.measured_wall_seconds_total, 6
            )
            last = self._measured_last or {}
            for key in (
                "measured_mfu", "measured_hbm_frac", "measured_device_frac",
                "measured_modeled_mfu_ratio", "measured_top_kernel_share",
            ):
                out[key] = last.get(key, 0.0)
        return out

    def histogram(self, phase: str) -> Tuple[Tuple[float, ...], List[int]]:
        """(bucket upper bounds, counts incl. +Inf) for one phase; the
        ``"host_gap"`` pseudo-phase returns the decode host-gap histogram."""
        h = self._gap if phase == "host_gap" else self._hists[phase]
        return h.buckets, list(h.counts)

    def ring_snapshot(self) -> dict:
        """Incident-bundle view of the flight recorder: the recent-step
        ring verbatim plus the host-gap and compile evidence — enough for
        ``tools/autopsy.py`` to reconstruct "what the engine was doing in
        the seconds before the trigger" without the live process."""
        now = time.monotonic()
        return {
            "recent_steps": self.recent_steps(),
            "last_step_phase": self.last_step_phase,
            "last_step_age_s": (
                round(now - self.last_step_ts, 3) if self.last_step_ts is not None else None
            ),
            "host_gap": {
                "events": self._gap.total,
                "sum_s": round(self._gap.sum_s, 6),
                "p50_s": round(self._gap.percentile(0.5), 6),
                "p99_s": round(self._gap.percentile(0.99), 6),
            },
            "compiles_total": self.compiles_total,
            "compiles_after_warmup_total": self.compiles_after_warmup_total,
            "post_warmup_keys": [str(k) for k in self.post_warmup_keys[-16:]],
        }
