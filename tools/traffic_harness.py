"""Million-user traffic harness: drive a mocker fleet through load shapes
and close the planner loop under them.

"Millions of users" as a measured curve, not a claim: this harness offers
seeded open-loop traffic (Poisson arrivals — the superposition of a huge
independent user population) in the shapes production fleets actually see:

- **diurnal** — a day compressed to ``duration_s``: trough → crest → trough
  (raised-cosine), the shape the seasonal predictors must track;
- **flash** — flat baseline with a step to ``peak_rate`` (the flash crowd);
- **ramp** — linear trough→crest (the constant predictor's lag test);
- **noisy_flat** — flat with seeded multiplicative noise (the hysteresis
  test: quantile jitter must NOT flap the fleet).

ISL/OSL and the prefix-share ratio drift across the run (``isl_end`` etc.),
so prefill and decode demand move *independently* — exactly what forces
coordinated-but-independent pool scaling.

Requests traverse the real wire path disaggregated: a **prefill leg**
(``max_tokens=1``, KV-routed so same-prefix bursts concentrate and build
per-worker warmth) and a **decode leg** (``prefill_done`` — the mocker
admits it as transferred KV, simulating decode cost only). Both legs ride
``Migration``-wrapped KV routers, so drains and injected crashes replay
losslessly; with ``token_rule="position"`` every surviving request's token
stream is *bit-checkable* against its expected positions — the zero-token-
loss assertion is exact, not statistical.

``run_autoscale_bench`` stands up the whole plane in one process — fleet
(planner/fleet.py), metrics aggregator (multi-endpoint scrape), Prometheus
observer over a real HTTP /metrics, AutoscaleController — runs the
harness against it, optionally arms a chaos scenario (runtime/faults.py)
the moment the first scale event lands, and reports SLO-attainment +
goodput curves per window plus the controller's convergence vs the
capacity oracle (``tests/test_autoscale.py`` holds it to them).

CLI::

    python -m tools.traffic_harness --pattern diurnal --duration 30 \
        --base-rate 2 --peak-rate 10 --seed 0 --out autoscale.json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import random
import sys
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.runtime.logging import get_logger

logger = get_logger(__name__)


# --- offered load -------------------------------------------------------------
@dataclass
class Offered:
    rate: float  # req/s
    isl: int
    osl: int
    prefix_ratio: float


@dataclass
class TrafficPattern:
    kind: str = "diurnal"  # diurnal | flash | ramp | noisy_flat
    duration_s: float = 30.0
    base_rate: float = 2.0
    peak_rate: float = 10.0
    period_s: float = 0.0  # diurnal period; 0 = one full day over duration_s
    flash_at: float = 0.4  # flash window start/width, fractions of duration
    flash_len: float = 0.2
    isl: int = 96
    isl_end: Optional[int] = None  # drift targets; None = constant
    osl: int = 16
    osl_end: Optional[int] = None
    prefix_ratio: float = 0.5
    prefix_ratio_end: Optional[float] = None
    noise: float = 0.0  # multiplicative rate noise amplitude (seeded, per-second)
    seed: int = 0

    def _frac(self, t: float) -> float:
        return min(max(t / self.duration_s, 0.0), 1.0) if self.duration_s > 0 else 0.0

    def _drift(self, start: float, end: Optional[float], t: float) -> float:
        return start if end is None else start + (end - start) * self._frac(t)

    def rate(self, t: float) -> float:
        lo, hi = self.base_rate, self.peak_rate
        if self.kind == "diurnal":
            period = self.period_s or self.duration_s
            r = lo + (hi - lo) * 0.5 * (1.0 - math.cos(2.0 * math.pi * t / period))
        elif self.kind == "flash":
            f = self._frac(t)
            r = hi if self.flash_at <= f < self.flash_at + self.flash_len else lo
        elif self.kind == "ramp":
            r = lo + (hi - lo) * self._frac(t)
        elif self.kind == "noisy_flat":
            r = lo
        else:
            raise ValueError(f"unknown pattern kind {self.kind!r}")
        if self.noise > 0:
            # Deterministic per-second jitter: a pure function of (seed, ⌊t⌋)
            # so two runs offer the identical load curve.
            jitter = random.Random((self.seed, int(t))).uniform(-self.noise, self.noise)
            r *= 1.0 + jitter
        return max(r, 0.0)

    def offered(self, t: float) -> Offered:
        return Offered(
            rate=self.rate(t),
            isl=int(round(self._drift(self.isl, self.isl_end, t))),
            osl=int(round(self._drift(self.osl, self.osl_end, t))),
            prefix_ratio=self._drift(self.prefix_ratio, self.prefix_ratio_end, t),
        )


class PromptFactory:
    """Deterministic prompts with a controllable shared-prefix ratio.

    ``groups`` hot prefixes model the popular system-prompt/context heads a
    real population shares; the suffix is unique per request. Token values
    are disjoint integer ranges so accidental overlap is impossible."""

    def __init__(self, block_size: int = 16, groups: int = 4):
        self.block_size = block_size
        self.groups = groups
        self._n = 0

    def make(self, rng: random.Random, isl: int, prefix_ratio: float) -> List[int]:
        bs = self.block_size
        plen = int(isl * prefix_ratio) // bs * bs  # block-aligned shared head
        g = rng.randrange(self.groups)
        prefix = [1_000_000 * (g + 1) + j for j in range(plen)]
        self._n += 1
        suffix = [500_000_000 + self._n * 8192 + j for j in range(max(isl - plen, 1))]
        return prefix + suffix


# --- per-request outcome ------------------------------------------------------
@dataclass
class Outcome:
    t: float  # arrival, seconds since harness start
    isl: int
    osl: int
    ttft_s: Optional[float] = None
    finish: Optional[str] = None
    error: Optional[str] = None
    tokens: int = 0
    token_exact: bool = False  # stream == expected positions, bit-for-bit

    @property
    def completed(self) -> bool:
        return self.error is None and self.finish in ("length", "stop")


class DisaggPath:
    """The two-leg disaggregated request path over mocker pools.

    TTFT is the prefill leg's first token (prompt processing happens
    there); the decode leg re-enters with ``prefill_done`` so the decode
    pool pays decode cost only. With ``token_rule="position"`` the decode
    stream must be exactly ``[isl, isl+1, ...]`` — surviving a drain or an
    injected crash with anything else is token loss and is counted."""

    def __init__(self, prefill_engine, decode_engine, *, request_timeout_ms: float = 0.0):
        self.prefill_engine = prefill_engine
        self.decode_engine = decode_engine
        self.request_timeout_ms = request_timeout_ms

    def _req(self, tokens: List[int], max_tokens: int, **extra: Any) -> dict:
        stop: Dict[str, Any] = {"max_tokens": max_tokens}
        if self.request_timeout_ms:
            stop["deadline_ms"] = self.request_timeout_ms
        return {
            "token_ids": list(tokens),
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": stop,
            **extra,
        }

    async def request(self, tokens: List[int], osl: int, t: float) -> Outcome:
        from dynamo_tpu.runtime.engine import Context

        out = Outcome(t=t, isl=len(tokens), osl=osl)
        t0 = time.monotonic()
        try:
            async for item in self.prefill_engine.generate(
                self._req(tokens, 1), Context()
            ):
                data = item.data if hasattr(item, "data") else item
                if isinstance(data, dict) and data.get("token_ids"):
                    if out.ttft_s is None:
                        out.ttft_s = time.monotonic() - t0
                if isinstance(data, dict) and data.get("finish_reason"):
                    break
            got: List[int] = []
            finish = None
            async for item in self.decode_engine.generate(
                self._req(tokens, osl, prefill_done=True), Context()
            ):
                data = item.data if hasattr(item, "data") else item
                if not isinstance(data, dict):
                    continue
                got.extend(data.get("token_ids") or ())
                if data.get("finish_reason"):
                    finish = data["finish_reason"]
                    break
            out.finish = finish
            out.tokens = len(got)
            expected = list(range(len(tokens), len(tokens) + osl))
            out.token_exact = got == expected[: len(got)] and (
                finish != "length" or len(got) == osl
            )
        except Exception as e:  # noqa: BLE001 — the harness counts, never masks
            out.error = f"{type(e).__name__}: {e}"
        return out


# --- the harness --------------------------------------------------------------
class TrafficHarness:
    """Seeded open-loop arrival process over a request path."""

    def __init__(
        self,
        path: DisaggPath,
        pattern: TrafficPattern,
        *,
        block_size: int = 16,
        prefix_groups: int = 4,
    ):
        self.path = path
        self.pattern = pattern
        self.prompts = PromptFactory(block_size=block_size, groups=prefix_groups)
        self.outcomes: List[Outcome] = []

    async def run(self) -> List[Outcome]:
        rng = random.Random(self.pattern.seed)
        start = time.monotonic()
        tasks: List[asyncio.Task] = []
        t = 0.0
        while True:
            rate = max(self.pattern.rate(t), 1e-3)
            t += rng.expovariate(rate)
            if t >= self.pattern.duration_s:
                break
            off = self.pattern.offered(t)
            tokens = self.prompts.make(rng, off.isl, off.prefix_ratio)
            now_rel = time.monotonic() - start
            if t > now_rel:
                await asyncio.sleep(t - now_rel)
            tasks.append(asyncio.create_task(self.path.request(tokens, off.osl, t)))
        if tasks:
            self.outcomes = list(await asyncio.gather(*tasks))
        return self.outcomes

    # --- aggregation -------------------------------------------------------
    def windows(self, window_s: float = 2.0, slo_ttft_ms: float = 0.0) -> List[dict]:
        """SLO-attainment and goodput curves across the run, per window."""
        if not self.outcomes:
            return []
        n_win = max(1, math.ceil(self.pattern.duration_s / window_s))
        wins: List[dict] = []
        for w in range(n_win):
            lo, hi = w * window_s, (w + 1) * window_s
            rows = [o for o in self.outcomes if lo <= o.t < hi]
            done = [o for o in rows if o.completed]
            ttfts = sorted(o.ttft_s for o in done if o.ttft_s is not None)

            def pct(p: float) -> Optional[float]:
                if not ttfts:
                    return None
                return ttfts[min(int(p * len(ttfts)), len(ttfts) - 1)]

            attained = [
                o for o in done
                if not slo_ttft_ms or (o.ttft_s or 0.0) * 1000.0 <= slo_ttft_ms
            ]
            wins.append({
                "t": lo,
                "offered_rate": round(self.pattern.rate((lo + hi) / 2), 3),
                "sent": len(rows),
                "completed": len(done),
                "errors": sum(1 for o in rows if o.error is not None),
                "ttft_p50_ms": round(pct(0.50) * 1000, 1) if ttfts else None,
                "ttft_p99_ms": round(pct(0.99) * 1000, 1) if ttfts else None,
                "slo_attained": len(attained),
                "slo_attainment": round(len(attained) / len(done), 4) if done else None,
                "goodput_req_s": round(len(attained) / window_s, 3),
                "goodput_tok_s": round(sum(o.tokens for o in attained) / window_s, 1),
            })
        return wins

    def totals(self) -> dict:
        rows = self.outcomes
        done = [o for o in rows if o.completed]
        return {
            "requests": len(rows),
            "completed": len(done),
            "errors": sum(1 for o in rows if o.error is not None),
            "timeouts": sum(1 for o in rows if o.finish == "timeout"),
            "cancelled": sum(1 for o in rows if o.finish == "cancelled"),
            # Completed (surviving) requests whose token stream diverged
            # from the expected positions: MUST be zero under drains,
            # migrations, and injected crashes.
            "token_loss": sum(1 for o in done if not o.token_exact),
        }


# --- the closed-loop autoscale bench ------------------------------------------
@dataclass
class AutoscaleBenchConfig:
    pattern: TrafficPattern = field(default_factory=TrafficPattern)
    adjustment_interval_s: float = 1.5
    scrape_interval_s: float = 0.5
    scale_cooldown_s: float = 3.0
    min_prefill: int = 1
    max_prefill: int = 6
    min_decode: int = 1
    max_decode: int = 6
    slo_ttft_ms: float = 1500.0
    slo_tpot_ms: float = 120.0
    drain_timeout_s: float = 6.0
    utilization: float = 0.8
    # Chaos: armed the moment the first scale event lands (a crash DURING a
    # scale event); empty string disables.
    chaos_spec: str = '[{"site": "worker.step", "kind": "crash", "after": 3, "count": 1}]'
    chaos_seed: int = 0
    settle_s: float = 2.0  # post-pattern grace for stragglers

    def prefill_args(self):
        from dynamo_tpu.llm.mocker import MockEngineArgs

        # Prefill-tuned: compute-bound prompt processing dominates
        # (2 ms/token ⇒ ~500 tok/s/worker), token emission fast.
        return MockEngineArgs(
            prefill_base_ms=1.0, prefill_per_token_us=2000.0,
            itl_base_ms=2.0, itl_per_seq_ms=0.1, max_batch=16,
            num_blocks=512, token_rule="position",
            slo_ttft_ms=self.slo_ttft_ms, slo_tpot_ms=None,
        )

    def decode_args(self):
        from dynamo_tpu.llm.mocker import MockEngineArgs

        # Decode-tuned: bandwidth-bound steps (~45 ms at b4 ⇒ ~90 tok/s/
        # worker), prefill legs never land here (prefill_done).
        return MockEngineArgs(
            prefill_base_ms=0.5, prefill_per_token_us=200.0,
            itl_base_ms=40.0, itl_per_seq_ms=1.0, max_batch=4,
            num_blocks=512, token_rule="position",
            slo_ttft_ms=None, slo_tpot_ms=self.slo_tpot_ms,
        )


def capacity_oracle(cfg: AutoscaleBenchConfig, offered: Offered) -> Dict[str, int]:
    """Pool sizes the capacity model implies for the TRUE offered load —
    what the controller should converge to from observed signals alone."""
    from dynamo_tpu.planner.controller import MockerCapacityModel

    model = MockerCapacityModel(
        cfg.prefill_args(), decode_args=cfg.decode_args(), utilization=cfg.utilization
    )
    want = model.required(offered.rate, offered.isl, offered.osl)
    want["prefill"] = max(cfg.min_prefill, min(cfg.max_prefill, want["prefill"]))
    want["decode"] = max(cfg.min_decode, min(cfg.max_decode, want["decode"]))
    return want


async def run_autoscale_bench(cfg: Optional[AutoscaleBenchConfig] = None) -> dict:
    """Stand up the full autoscaling plane in-process, run the harness
    against it, and report the closed-loop curves."""
    from dynamo_tpu.llm.kv_router import KvPushRouter, KvRouterConfig
    from dynamo_tpu.llm.migration import Migration
    from dynamo_tpu.metrics_aggregator import MetricsAggregator
    from dynamo_tpu.planner.controller import (
        AutoscaleController,
        ControllerConfig,
        MockerCapacityModel,
    )
    from dynamo_tpu.planner.fleet import AutoscaleLoop, MockerFleet
    from dynamo_tpu.planner.observer import PrometheusObserver
    from dynamo_tpu.runtime import faults
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.health import SystemHealth, SystemStatusServer

    cfg = cfg or AutoscaleBenchConfig()
    drt = await DistributedRuntime.detached()
    server = None
    agg = None
    routers: List[Any] = []
    try:
        fleet = MockerFleet(
            drt, "autoscale",
            make_args=lambda c: cfg.prefill_args() if c == "prefill" else cfg.decode_args(),
            drain_timeout_s=cfg.drain_timeout_s,
        )
        for _ in range(cfg.min_prefill):
            await fleet.add_worker("prefill")
        for _ in range(cfg.min_decode):
            await fleet.add_worker("decode")

        controller = AutoscaleController(
            ControllerConfig(
                min_prefill=cfg.min_prefill, max_prefill=cfg.max_prefill,
                min_decode=cfg.min_decode, max_decode=cfg.max_decode,
                scale_cooldown_s=cfg.scale_cooldown_s,
                scale_up_stable_intervals=1, scale_down_stable_intervals=2,
                ttft_sla_s=cfg.slo_ttft_ms / 1000.0, tpot_sla_s=cfg.slo_tpot_ms / 1000.0,
                load_predictor="trend",
            ),
            MockerCapacityModel(
                cfg.prefill_args(), decode_args=cfg.decode_args(),
                utilization=cfg.utilization,
            ),
        )
        await fleet.serve_planner(controller)

        # Aggregator scrapes both pools + the planner; the observer reads
        # its real /metrics exposition over HTTP — the production loop.
        agg = MetricsAggregator(
            drt, "autoscale", "prefill", "generate",
            interval_s=cfg.scrape_interval_s,
            extra_endpoints=["autoscale/decode/generate", "autoscale/planner/control"],
        )
        await agg.start()
        health = SystemHealth()
        health.set_system_ready()
        server = SystemStatusServer(health, metrics=agg.registry)
        server.config.port = 0
        await server.start()
        observer = PrometheusObserver(f"http://127.0.0.1:{server.port}/metrics")

        prefill_client = await fleet.endpoint("prefill").client()
        decode_client = await fleet.endpoint("decode").client()
        await prefill_client.wait_for_instances(cfg.min_prefill, timeout=10)
        await decode_client.wait_for_instances(cfg.min_decode, timeout=10)
        prefill_router = await KvPushRouter.create(prefill_client, KvRouterConfig(block_size=16))
        decode_router = await KvPushRouter.create(decode_client, KvRouterConfig(block_size=16))
        routers = [prefill_router, decode_router]

        def router_stats() -> dict:
            merged: Dict[int, int] = {}
            for r in routers:
                for wid, n in r.stats()["cached_tokens_by_worker"].items():
                    merged[wid] = merged.get(wid, 0) + n
            return {"cached_tokens_by_worker": merged}

        loop = AutoscaleLoop(
            controller, fleet, observer.observe,
            interval_s=cfg.adjustment_interval_s, router_stats_fn=router_stats,
        )

        path = DisaggPath(
            Migration(3).attach(prefill_router), Migration(3).attach(decode_router)
        )
        harness = TrafficHarness(path, cfg.pattern)

        timeline: List[dict] = []
        chaos_armed_at: Optional[float] = None

        async def control() -> None:
            nonlocal chaos_armed_at
            start = time.monotonic()
            while time.monotonic() - start < cfg.pattern.duration_s + cfg.settle_s:
                await asyncio.sleep(cfg.adjustment_interval_s)
                decisions = await loop.step()
                t_rel = time.monotonic() - start
                timeline.append({
                    "t": round(t_rel, 2),
                    "prefill": fleet.size("prefill"),
                    "decode": fleet.size("decode"),
                    "targets": dict(controller._targets),
                    "drains_in_flight": {
                        c: fleet.drains_in_flight(c) for c in ("prefill", "decode")
                    },
                    "actions": [
                        {"pool": d.pool, "action": d.action, "count": d.count,
                         "victims": [f"{v:x}" for v in d.victims]}
                        for d in decisions if d.action != "hold"
                    ],
                })
                if (
                    cfg.chaos_spec
                    and chaos_armed_at is None
                    and any(d.action != "hold" for d in decisions)
                ):
                    # First scale event just landed: arm the chaos scenario
                    # NOW so the fault fires while the fleet is mid-change.
                    faults.arm_from_spec(cfg.chaos_spec, seed=cfg.chaos_seed)
                    chaos_armed_at = t_rel
                    logger.info("chaos armed at t=%.1fs (scale event in flight)", t_rel)

        control_task = asyncio.create_task(control())
        await harness.run()
        await asyncio.sleep(cfg.settle_s)
        control_task.cancel()
        try:
            await control_task
        except asyncio.CancelledError:
            pass

        chaos = {
            "armed_at_s": chaos_armed_at,
            "injections": faults.stats().get("faults_injected_total", 0),
            "log": [dict(r) for r in (faults.get_injector().log if faults.get_injector() else [])],
        }
        faults.disarm()

        final_offered = cfg.pattern.offered(cfg.pattern.duration_s)
        oracle = capacity_oracle(cfg, final_offered)
        final = {
            "prefill": fleet.size("prefill"),
            "decode": fleet.size("decode"),
            "oracle_prefill": oracle["prefill"],
            "oracle_decode": oracle["decode"],
            "converged": (
                abs(fleet.size("prefill") - oracle["prefill"]) <= 1
                and abs(fleet.size("decode") - oracle["decode"]) <= 1
            ),
        }
        peak_offered = max(
            (cfg.pattern.offered(w["t"]) for w in timeline or [{"t": 0.0}]),
            key=lambda o: o.rate, default=final_offered,
        ) if timeline else final_offered
        windows = harness.windows(
            window_s=max(cfg.adjustment_interval_s, 1.0), slo_ttft_ms=cfg.slo_ttft_ms
        )
        done = [o for o in harness.outcomes if o.completed]
        attained = sum(w["slo_attained"] for w in windows)
        report = {
            "pattern": asdict(cfg.pattern),
            "windows": windows,
            "timeline": timeline,
            "totals": harness.totals(),
            "slo_attainment": round(attained / len(done), 4) if done else None,
            "final": final,
            "peak_oracle": capacity_oracle(cfg, peak_offered),
            "max_pools": {
                "prefill": max((t["prefill"] for t in timeline), default=cfg.min_prefill),
                "decode": max((t["decode"] for t in timeline), default=cfg.min_decode),
            },
            "chaos": chaos,
            "planner": controller.to_stats(),
            "fleet": fleet.summary(),
        }
        for r in routers:
            await r.close()
        routers = []
        await fleet.shutdown()
        return report
    finally:
        faults.disarm()
        for r in routers:
            try:
                await r.close()
            except Exception:  # noqa: BLE001
                pass
        if agg is not None:
            await agg.stop()
        if server is not None:
            await server.stop()
        await drt.shutdown()


def main() -> None:
    p = argparse.ArgumentParser(description="mocker-fleet traffic harness / autoscale bench")
    p.add_argument("--pattern", choices=["diurnal", "flash", "ramp", "noisy_flat"],
                   default="diurnal")
    p.add_argument("--duration", type=float, default=30.0)
    p.add_argument("--base-rate", type=float, default=2.0)
    p.add_argument("--peak-rate", type=float, default=10.0)
    p.add_argument("--isl", type=int, default=96)
    p.add_argument("--isl-end", type=int, default=None)
    p.add_argument("--osl", type=int, default=16)
    p.add_argument("--osl-end", type=int, default=None)
    p.add_argument("--prefix-ratio", type=float, default=0.5)
    p.add_argument("--noise", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--adjustment-interval", type=float, default=1.5)
    p.add_argument("--scale-cooldown-s", type=float, default=3.0)
    p.add_argument("--no-chaos", action="store_true")
    p.add_argument("--out", default=None, help="write the report JSON here (default stdout)")
    args = p.parse_args()

    cfg = AutoscaleBenchConfig(
        pattern=TrafficPattern(
            kind=args.pattern, duration_s=args.duration,
            base_rate=args.base_rate, peak_rate=args.peak_rate,
            isl=args.isl, isl_end=args.isl_end, osl=args.osl, osl_end=args.osl_end,
            prefix_ratio=args.prefix_ratio, noise=args.noise, seed=args.seed,
        ),
        adjustment_interval_s=args.adjustment_interval,
        scale_cooldown_s=args.scale_cooldown_s,
        chaos_spec="" if args.no_chaos else AutoscaleBenchConfig.chaos_spec,
    )
    report = asyncio.run(run_autoscale_bench(cfg))
    text = json.dumps(report, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    else:
        print(text)


if __name__ == "__main__":
    main()
