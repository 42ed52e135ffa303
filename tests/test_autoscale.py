"""Closed-loop SLA autoscaler tests (PR 11).

Three layers, matching the subsystem's split:

- **decision table** — the controller is a pure function over replayed
  ``ObservedLoad`` sequences, so ramp-up / ramp-down / flash-crowd /
  noisy-flat each assert the EXACT add/drain decision sequence, that
  hysteresis suppresses flapping, cooldown suppresses echoes, and the
  drain debounce never stacks scale-downs;
- **fleet** — decisions become real in-process mocker launches/drains over
  the wire path, including the slow-drain chaos case and coldest-worker
  (KV-warmth) victim selection;
- **closed loop** — the shortened traffic-harness ramp drives the whole
  plane (fleet → aggregator → observer → controller → fleet) with a chaos
  fault firing during a scale event: pools converge to the capacity
  oracle, SLO attainment holds, zero token loss on surviving requests.
"""

import asyncio
import math
import random

import pytest

from dynamo_tpu.planner.controller import (
    DECODE,
    PREFILL,
    AutoscaleController,
    ControllerConfig,
    FleetView,
    MockerCapacityModel,
    StaticCapacityModel,
    WorkerView,
    rank_coldest,
)
from dynamo_tpu.planner.load_predictor import (
    ConstantPredictor,
    SeasonalTrendPredictor,
    TrendPredictor,
    make_predictor,
)
from dynamo_tpu.planner.planner_core import ObservedLoad


def make_controller(**overrides) -> AutoscaleController:
    kw = dict(
        min_prefill=1, max_prefill=8, min_decode=1, max_decode=8,
        scale_cooldown_s=10.0, scale_up_stable_intervals=1,
        scale_down_stable_intervals=2, max_step=2,
        load_predictor="constant",  # deterministic replay
    )
    kw.update(overrides)
    cfg = ControllerConfig(**kw)
    # prefill 400 tok/s, decode 80 tok/s per worker; utilization 1.0 keeps
    # the expected sizes mental-math-exact.
    return AutoscaleController(cfg, StaticCapacityModel(400.0, 80.0, utilization=1.0))


def view_of(prefill: int, decode: int, drains=None) -> FleetView:
    return FleetView(
        pools={
            PREFILL: [WorkerView(worker_id=100 + i) for i in range(prefill)],
            DECODE: [WorkerView(worker_id=200 + i) for i in range(decode)],
        },
        drains_in_flight=drains or {},
    )


def load(rate, isl=100, osl=16, **kw) -> ObservedLoad:
    return ObservedLoad(request_rate=rate, avg_isl=isl, avg_osl=osl, **kw)


def actions(decisions):
    return [(d.pool, d.action, d.count) for d in decisions]


# --- decision table -----------------------------------------------------------
def test_decision_table_ramp_up_down():
    """Replayed ramp: exact add sequence on the way up (slice-granular,
    max_step-capped), hysteresis-delayed drains on the way down, cooldown
    suppressing the echo in between."""
    c = make_controller()
    sizes = {PREFILL: 1, DECODE: 1}

    def step(rate, t, drains=None):
        ds = c.decide(load(rate), view_of(sizes[PREFILL], sizes[DECODE], drains), t)
        for d in ds:
            if d.action != "hold":
                sizes[d.pool] = d.target
        return ds

    # rate 1: want (1,1) == current -> hold.
    assert actions(step(1.0, t=0.0)) == [(PREFILL, "hold", 0), (DECODE, "hold", 0)]
    # rate 8: want (ceil(800/400)=2, ceil(128/80)=2) -> immediate add (up_stable=1).
    assert actions(step(8.0, t=20.0)) == [(PREFILL, "add", 1), (DECODE, "add", 1)]
    # rate 16: want (4,4) from (2,2) -> add capped at max_step=2.
    assert actions(step(16.0, t=40.0)) == [(PREFILL, "add", 2), (DECODE, "add", 2)]
    assert sizes == {PREFILL: 4, DECODE: 4}
    # steady: hold.
    assert actions(step(16.0, t=60.0)) == [(PREFILL, "hold", 0), (DECODE, "hold", 0)]
    # rate 2: want (1,1) — hysteresis needs 2 consecutive under-windows.
    assert actions(step(2.0, t=80.0)) == [(PREFILL, "hold", 0), (DECODE, "hold", 0)]
    ds = step(2.0, t=100.0)
    assert actions(ds) == [(PREFILL, "drain", 2), (DECODE, "drain", 2)]
    assert all(d.victims for d in ds if d.action == "drain")
    assert sizes == {PREFILL: 2, DECODE: 2}
    # still low, stable again — but inside the 10s cooldown: suppressed.
    step(2.0, t=104.0)
    ds = step(2.0, t=108.0)
    assert actions(ds) == [(PREFILL, "hold", 0), (DECODE, "hold", 0)]
    assert c.cooldown_suppressed_total >= 2
    # cooldown expired: the final drain lands.
    ds = step(2.0, t=111.0)
    assert actions(ds) == [(PREFILL, "drain", 1), (DECODE, "drain", 1)]
    assert sizes == {PREFILL: 1, DECODE: 1}
    # Counters are per-pool actions: 2 up + 2 down passes × both pools.
    assert c.scale_up_total == 4 and c.scale_down_total == 4


def test_noisy_flat_does_not_flap():
    """Quantile/rate noise oscillating the desired size between 2 and 3
    every window must produce ZERO fleet actions once hysteresis requires
    consecutive agreement in BOTH directions — alternating windows never
    build a streak."""
    c = make_controller(scale_up_stable_intervals=2, scale_down_stable_intervals=2)
    sizes = {PREFILL: 2, DECODE: 2}
    rng = random.Random(7)
    moved = []
    for i in range(20):
        # rate alternates so desired prefill flips 2 <-> 3 (800±200 / 400).
        rate = 8.0 + (2.0 if i % 2 else -2.0) * rng.uniform(0.8, 1.0)
        ds = c.decide(load(rate, isl=100, osl=20),
                      view_of(sizes[PREFILL], sizes[DECODE]), float(i * 10))
        for d in ds:
            if d.action != "hold":
                sizes[d.pool] = d.target
                moved.append(d)
    assert moved == [], [f"{d.pool}:{d.action}" for d in moved]
    assert c.hysteresis_suppressed_total > 0


def test_flash_crowd_sequence():
    """Flash crowd: immediate scale-up on the spike window, cooldown holds
    through the spike, hysteresis-delayed drain after it passes."""
    c = make_controller(scale_cooldown_s=15.0)
    sizes = {PREFILL: 1, DECODE: 1}

    def step(rate, t):
        ds = c.decide(load(rate), view_of(sizes[PREFILL], sizes[DECODE]), t)
        for d in ds:
            if d.action != "hold":
                sizes[d.pool] = d.target
        return ds

    step(1.0, t=0.0)
    assert actions(step(20.0, t=10.0))[0] == (PREFILL, "add", 2)  # spike hits
    assert actions(step(20.0, t=20.0)) == [(PREFILL, "hold", 0), (DECODE, "hold", 0)]  # cooldown
    assert actions(step(20.0, t=26.0))[0] == (PREFILL, "add", 2)  # still hot, cooldown over
    # Spike gone: two stable windows + cooldown before the first drain.
    step(1.0, t=42.0)
    ds = step(1.0, t=44.0)
    assert [a for a in actions(ds) if a[1] == "drain"], actions(ds)


def test_drain_debounce_blocks_second_scale_down():
    """Never a second scale-down while a drain is still in flight — and the
    held decision lands once the drain clears."""
    c = make_controller(scale_cooldown_s=0.0, scale_down_stable_intervals=1)
    # Demand wants 1 prefill; current 4, a drain from the previous decision
    # still in flight.
    ds = c.decide(load(1.0), view_of(4, 1, drains={PREFILL: 1}), 0.0)
    pre = next(d for d in ds if d.pool == PREFILL)
    assert pre.action == "hold" and "drain in flight" in pre.reason
    assert c.drain_debounced_total == 1
    # Drain landed: the scale-down proceeds (victims ranked).
    ds = c.decide(load(1.0), view_of(3, 1, drains={PREFILL: 0}), 1.0)
    pre = next(d for d in ds if d.pool == PREFILL)
    assert pre.action == "drain" and pre.count == 2 and len(pre.victims) == 2


def test_sla_feedback_bumps_pressured_pool():
    """Closed-loop corrections: a TTFT/queue breach bumps prefill, a TPOT
    breach bumps decode, KV pressure bumps decode — independent pools."""
    c = make_controller(ttft_sla_s=0.2, tpot_sla_s=0.05, slo_floor=0.9)
    base = c.desired_sizes(load(4.0))  # want (1, 1) at rate 4
    assert base == {PREFILL: 1, DECODE: 1}
    hot_ttft = c.desired_sizes(load(4.0, ttft_p99=0.5, slo_attainment=0.5))
    assert hot_ttft[PREFILL] == base[PREFILL] + 1
    hot_tpot = c.desired_sizes(load(4.0, tpot_p99=0.2))
    assert hot_tpot[DECODE] == base[DECODE] + 1
    hot_kv = c.desired_sizes(load(4.0, kv_util=0.95))
    assert hot_kv[DECODE] == base[DECODE] + 1


def test_rank_coldest_prefers_router_reuse_then_engine_warmth():
    workers = [
        WorkerView(1, kv_util=0.9, kv_warmth=0.1, cached_tokens_total=0),     # cold, busy
        WorkerView(2, kv_util=0.1, kv_warmth=0.8, cached_tokens_total=4096),  # warm (router-proven)
        WorkerView(3, kv_util=0.1, kv_warmth=0.5, cached_tokens_total=0),     # lukewarm engine-side
        WorkerView(4, kv_util=0.0, kv_warmth=0.0, cached_tokens_total=0, draining=True),
    ]
    # Draining worker is never a candidate; router-proven reuse dominates:
    # worker 2 must be the LAST drain candidate.
    order = rank_coldest(workers, 3)
    assert 4 not in order
    assert order[-1] == 2 and 2 not in order[:2]
    # Exact order follows the documented composite score (ties break by id).
    scores = {w.worker_id: w.warmth_score(4096) for w in workers[:3]}
    assert order == sorted(scores, key=lambda k: (scores[k], k))


def test_budget_clamp_preserves_ratio():
    c = make_controller(max_total=4)
    want = c.desired_sizes(load(40.0, isl=100, osl=40))  # raw: pre 10, dec 20 -> clamped
    assert want[PREFILL] + want[DECODE] <= 4 + 1
    assert want[PREFILL] >= 1 and want[DECODE] >= 1
    assert want[DECODE] >= want[PREFILL]  # ratio preserved under the clamp


# --- predictors ---------------------------------------------------------------
def test_trend_predictor_fixes_constant_ramp_lag():
    """On a linear ramp the constant predictor is exactly one interval
    behind; the trend predictor's one-step-ahead extrapolation is not."""
    const, trend = ConstantPredictor(), TrendPredictor()
    slope = 3.0
    const_err = trend_err = 0.0
    for i in range(20):
        v = slope * i
        const.observe(v)
        trend.observe(v)
        nxt = slope * (i + 1)
        const_err = abs(const.predict() - nxt)
        trend_err = abs(trend.predict() - nxt)
    assert const_err == pytest.approx(slope)  # the structural one-interval lag
    assert trend_err < 0.2 * const_err


def test_trend_predictor_tracks_diurnal_ramp():
    """Against the harness's diurnal shape: mean absolute one-step-ahead
    error of the trend predictor beats the constant predictor on the ramp
    segments (the bias the satellite names)."""
    from tools.traffic_harness import TrafficPattern

    pat = TrafficPattern(kind="diurnal", duration_s=100.0, base_rate=2.0, peak_rate=20.0)
    const, trend = ConstantPredictor(), TrendPredictor()
    errs = {"const": [], "trend": []}
    ts = [float(t) for t in range(0, 100, 2)]
    for t in ts:
        v = pat.rate(t)
        const.observe(v)
        trend.observe(v)
        nxt = pat.rate(t + 2)
        errs["const"].append(abs(const.predict() - nxt))
        errs["trend"].append(abs(trend.predict() - nxt))
    # Strictly better over the whole day; the big wins are on the ramp
    # segments (the crest/trough turns give some back — that is what the
    # seasonal_trend mode is for).
    assert sum(errs["trend"]) < 0.85 * sum(errs["const"])
    ramp = [i for i, t in enumerate(ts) if abs(math.sin(2 * math.pi * t / 100.0)) > 0.5]
    assert sum(errs["trend"][i] for i in ramp) < 0.6 * sum(errs["const"][i] for i in ramp)


def test_seasonal_trend_predictor():
    """Second day of a growing diurnal cycle: seasonal+trend anticipates
    the crest where trend-on-levels overshoots and seasonal-naive lags."""
    period = 24
    p = SeasonalTrendPredictor(period=period, trend_window=6)
    series = []
    for day in range(3):
        for h in range(period):
            v = (10 + 2 * day) * (1 - math.cos(2 * math.pi * h / period)) / 2
            series.append(v)
    errs = []
    for i, v in enumerate(series):
        p.observe(v)
        if i >= 2 * period and i + 1 < len(series):
            errs.append(abs(p.predict() - series[i + 1]))
    naive = make_predictor("seasonal", period=period)
    errs_naive = []
    for i, v in enumerate(series):
        naive.observe(v)
        if i >= 2 * period and i + 1 < len(series):
            errs_naive.append(abs(naive.predict() - series[i + 1]))
    assert sum(errs) < sum(errs_naive)


# --- planner_core satellites (CLI knob semantics) -----------------------------
async def test_planner_dry_run_and_cooldown():
    from dynamo_tpu.planner import (
        DecodeInterpolator,
        Planner,
        PlannerConfig,
        PrefillInterpolator,
        VirtualConnector,
    )

    prefill = PrefillInterpolator(isl=[128, 1024], ttft_ms=[20, 130],
                                  thpt_per_chip=[8000, 11000])
    decode = DecodeInterpolator(active_kv=[8, 512], context_len=[1024, 1024],
                                itl_ms=[5, 15], thpt_per_chip=[50, 600])

    loads = iter([
        ObservedLoad(request_rate=1.0, avg_isl=512, avg_osl=64),
        ObservedLoad(request_rate=30.0, avg_isl=1024, avg_osl=256),
        ObservedLoad(request_rate=30.0, avg_isl=1024, avg_osl=256),
    ])

    async def observe():
        return next(loads)

    # Dry run: decisions logged/counted, connector never driven.
    conn = VirtualConnector()
    p = Planner(PlannerConfig(dry_run=True, load_predictor="constant"),
                conn, prefill, decode, observe)
    await p.step()
    assert conn.history == [] and p.dry_run_decisions_total == 1

    # Cooldown: the second (different) plan inside the window is held.
    loads2 = iter([
        ObservedLoad(request_rate=1.0, avg_isl=512, avg_osl=64),
        ObservedLoad(request_rate=30.0, avg_isl=1024, avg_osl=256),
    ])

    async def observe2():
        return next(loads2)

    conn2 = VirtualConnector()
    p2 = Planner(PlannerConfig(scale_cooldown_s=3600.0, load_predictor="constant"),
                 conn2, prefill, decode, observe2)
    plan1 = await p2.step()
    held = await p2.step()  # burst arrives inside the cooldown -> held
    assert held == plan1 and p2.cooldown_holds_total == 1
    assert len(conn2.history) == 2  # only the first plan's two set_replicas

    # Per-pool max clamp.
    p3 = Planner(PlannerConfig(max_prefill_replicas=1, max_decode_replicas=2,
                               max_chip_budget=64),
                 VirtualConnector(), prefill, decode, None)
    plan = p3.compute_replicas(ObservedLoad(request_rate=1000.0, avg_isl=4096, avg_osl=512))
    assert plan.prefill <= 1 and plan.decode <= 2


# --- fleet: real launches/drains ----------------------------------------------
async def test_fleet_scale_and_coldest_drain_e2e():
    """Launch a 3-worker prefill pool, warm ONE worker with same-prefix
    traffic through the KV router, then scale down: the drained victim must
    be a cold worker, never the warm one — and the drain completes with the
    allocator clean."""
    from dynamo_tpu.llm.kv_router import KvPushRouter, KvRouterConfig
    from dynamo_tpu.llm.mocker import MockEngineArgs
    from dynamo_tpu.planner.fleet import MockerFleet
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context

    drt = await DistributedRuntime.detached()
    try:
        fleet = MockerFleet(
            drt, "fleete2e",
            make_args=lambda c: MockEngineArgs(speedup_ratio=100.0, num_blocks=128,
                                               token_rule="position"),
            drain_timeout_s=5.0,
        )
        for _ in range(3):
            await fleet.add_worker("prefill")
        client = await fleet.endpoint("prefill").client()
        await client.wait_for_instances(3, timeout=5)
        router = await KvPushRouter.create(client, KvRouterConfig(block_size=16))

        prefix = list(range(64))

        async def run_one(tokens):
            async for _ in router.generate(
                {"token_ids": tokens, "stop_conditions": {"max_tokens": 2}}, Context()
            ):
                pass

        await run_one(prefix + [900])
        await asyncio.sleep(0.3)  # KV events -> indexer
        for i in range(5):
            await run_one(prefix + [1000 + i])
        stats = router.stats()
        assert stats["cached_tokens_total"] > 0
        warm = max(stats["cached_tokens_by_worker"], key=stats["cached_tokens_by_worker"].get)

        view = fleet.view(router_stats=stats)
        victims = rank_coldest(view.pools["prefill"], 2)
        assert warm not in victims, (warm, victims)

        # Drain one cold worker through the fleet; debounce signal visible.
        task = fleet.drain_worker("prefill", victims[0])
        assert task is not None
        assert fleet.size("prefill") == 2
        await task
        assert fleet.drains_in_flight("prefill") == 0
        for _ in range(100):
            if len(client.instances) == 2:
                break
            await asyncio.sleep(0.02)
        assert len(client.instances) == 2
        # Warm worker still serving, and the fleet drains clean.
        assert any(w.worker_id == warm for w in fleet.pools["prefill"])
        await router.close()
        await fleet.shutdown()
        assert fleet.size("prefill") == 0
    finally:
        await drt.shutdown()


async def test_slow_drain_debounces_second_scale_down():
    """Slow-drain chaos: a long in-flight stream keeps the drain open; the
    controller must HOLD the next scale-down until the drain lands, then
    proceed — and the slow request survives token-exact (migration on
    sever)."""
    from dynamo_tpu.llm.kv_router import KvPushRouter, KvRouterConfig
    from dynamo_tpu.llm.migration import Migration
    from dynamo_tpu.llm.mocker import MockEngineArgs
    from dynamo_tpu.planner.fleet import MockerFleet
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.runtime.engine import Context

    drt = await DistributedRuntime.detached()
    try:
        fleet = MockerFleet(
            drt, "fleetslow",
            make_args=lambda c: MockEngineArgs(itl_base_ms=30.0, num_blocks=128,
                                               token_rule="position"),
            drain_timeout_s=8.0,
        )
        for _ in range(3):
            await fleet.add_worker("decode")
        client = await fleet.endpoint("decode").client()
        await client.wait_for_instances(3, timeout=5)
        router = await KvPushRouter.create(client, KvRouterConfig(block_size=16))
        engine = Migration(2).attach(router)

        # A slow stream (~1.2s) pinned to whichever worker the router picks.
        got = []

        async def slow_request():
            async for item in engine.generate(
                {"token_ids": list(range(10)), "stop_conditions": {"max_tokens": 40}},
                Context(),
            ):
                data = item.data if hasattr(item, "data") else item
                if isinstance(data, dict):
                    got.extend(data.get("token_ids") or ())

        stream = asyncio.create_task(slow_request())
        await asyncio.sleep(0.2)
        busy = [w.worker_id for w in fleet.pools["decode"]
                if w.engine.running or w.engine.waiting]
        assert busy, "slow stream should be in flight somewhere"

        c = make_controller(scale_cooldown_s=0.0, scale_down_stable_intervals=1,
                            max_step=1)
        # Scale-down #1: drain the busy worker (force victim via warmth: give
        # the others router-proven warmth so the busy one ranks coldest).
        stats = {"cached_tokens_by_worker": {
            w.worker_id: (0 if w.worker_id in busy else 4096)
            for w in fleet.pools["decode"]}}
        ds = c.decide(load(0.1, osl=8), fleet.view(stats), 0.0)
        dec = next(d for d in ds if d.pool == DECODE)
        assert dec.action == "drain" and dec.victims[0] == busy[0]
        await fleet.apply([dec])
        assert fleet.drains_in_flight("decode") == 1

        # Scale-down #2 while the drain is in flight: DEBOUNCED.
        ds = c.decide(load(0.1, osl=8), fleet.view(stats), 1.0)
        dec2 = next(d for d in ds if d.pool == DECODE)
        assert dec2.action == "hold" and "drain in flight" in dec2.reason
        assert c.drain_debounced_total == 1

        await fleet.wait_drains(timeout=12.0)
        await stream
        # Token-exact survival across the drain (finish or migrate).
        assert got == list(range(10, 50))

        # Drain landed: the next scale-down proceeds.
        ds = c.decide(load(0.1, osl=8), fleet.view(stats), 2.0)
        dec3 = next(d for d in ds if d.pool == DECODE)
        assert dec3.action == "drain" and dec3.count == 1
        await router.close()
        await fleet.shutdown()
    finally:
        await drt.shutdown()


async def test_planner_stats_flow_through_aggregator():
    """Controller counters/gauges reach Prometheus through the real scrape:
    fleet serves the planner endpoint, the aggregator's multi-endpoint
    scrape merges it, and the planner_* families render."""
    from dynamo_tpu.metrics_aggregator import MetricsAggregator
    from dynamo_tpu.planner.fleet import MockerFleet
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    drt = await DistributedRuntime.detached()
    try:
        fleet = MockerFleet(drt, "plagg")
        await fleet.add_worker("prefill")
        await fleet.add_worker("decode")
        c = make_controller()
        c.decide(load(8.0), fleet.view(), 0.0)
        await fleet.serve_planner(c)

        agg = MetricsAggregator(
            drt, "plagg", "prefill", "generate",
            extra_endpoints=["plagg/decode/generate", "plagg/planner/control"],
        )
        await agg.start()
        stats = await agg.scrape_once()
        # Both pool workers + the planner pseudo-worker.
        assert len(stats) == 3
        assert any("planner_decisions_total" in s for s in stats.values())
        assert any("kv_warmth" in s for s in stats.values())
        agg.export_stats(stats)
        text = agg.registry.render().decode()
        assert "dynamo_component_worker_planner_decisions_total" in text
        assert "dynamo_component_worker_planner_prefill_target" in text
        assert "dynamo_component_worker_kv_warmth" in text
        await agg.stop()
        await fleet.shutdown()
    finally:
        await drt.shutdown()


# --- the closed loop ----------------------------------------------------------
@pytest.mark.slow  # ~25s of real-time ramp (`-m slow` runs it); tier-1 keeps
# the fast decision/fleet layers.
async def test_autoscale_closed_loop_with_chaos():
    """Shortened harness diurnal ramp through the FULL plane. Asserts the
    acceptance criteria: independent pool growth, convergence to the
    capacity oracle at the trough, SLO attainment, a chaos fault fired
    during a scale event, and zero token loss on surviving requests."""
    from tools.traffic_harness import (
        AutoscaleBenchConfig,
        TrafficPattern,
        run_autoscale_bench,
    )

    cfg = AutoscaleBenchConfig(
        pattern=TrafficPattern(kind="diurnal", duration_s=16.0, base_rate=1.5,
                               peak_rate=8.0, isl=96, isl_end=144, osl=16, seed=0),
        adjustment_interval_s=1.5,
        scale_cooldown_s=3.0,
        settle_s=5.0,
    )
    report = await run_autoscale_bench(cfg)

    totals = report["totals"]
    assert totals["requests"] > 30
    assert totals["token_loss"] == 0, report["totals"]
    assert totals["errors"] == 0, report["totals"]

    # The planner really scaled both pools up and back down.
    planner = report["planner"]
    assert planner["planner_scale_up_total"] >= 2
    assert planner["planner_scale_down_total"] >= 1
    assert report["max_pools"]["prefill"] > 1
    assert report["max_pools"]["decode"] > 1
    # Peak capacity at least covered the oracle for the crest load.
    assert report["max_pools"]["prefill"] >= report["peak_oracle"]["prefill"]
    assert report["max_pools"]["decode"] >= report["peak_oracle"]["decode"]

    # Converged back to the oracle at the trough (±1).
    assert report["final"]["converged"], report["final"]

    # Chaos fired mid-scale-event; surviving requests stayed token-exact.
    assert report["chaos"]["armed_at_s"] is not None
    assert report["chaos"]["injections"] >= 1

    # SLO-attainment/goodput curves exist across the ramp and hold a floor.
    assert len(report["windows"]) >= 6
    assert report["slo_attainment"] is not None and report["slo_attainment"] >= 0.7
