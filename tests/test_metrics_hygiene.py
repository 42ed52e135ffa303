"""Metrics hygiene: instantiate every registry the serving roles create,
render them, and assert the exposition obeys the conventions Prometheus
tooling relies on — unique family names, counters ending in ``_total``,
histograms with explicitly declared (non-default) buckets — plus the
regression test for the ``_get_or_create`` label-mismatch trap."""

import re

import pytest

from dynamo_tpu.llm.discovery import ModelManager
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.metrics_aggregator import COUNTER_KEYS, GAUGE_KEYS, MetricsAggregator
from dynamo_tpu.runtime.metrics import MetricsRegistry

# prometheus_client's implicit default buckets: a histogram rendering these
# exact bounds almost certainly forgot to declare LLM-scale buckets.
_DEFAULT_LE = {
    "0.005", "0.01", "0.025", "0.05", "0.075", "0.1", "0.25", "0.5",
    "0.75", "1.0", "2.5", "5.0", "7.5", "10.0", "+Inf",
}


def parse_families(text: str):
    """{family_name: {"type": t, "samples": [...], "le": set()}} from the
    Prometheus text exposition."""
    fams = {}
    for line in text.splitlines():
        m = re.match(r"# TYPE (\S+) (\S+)", line)
        if m:
            fams[m.group(1)] = {"type": m.group(2), "samples": [], "le": set()}
            continue
        if line.startswith("#") or not line.strip():
            continue
        name = line.split("{")[0].split(" ")[0]
        fam_name = next((f for f in fams if name == f or name.startswith(f + "_")), None)
        if fam_name:
            fams[fam_name]["samples"].append(name)
            le = re.search(r'le="([^"]+)"', line)
            if le:
                fams[fam_name]["le"].add(le.group(1))
    return fams


def frontend_registry() -> MetricsRegistry:
    """HttpService's registry with every metric factory touched (the way a
    live frontend would after serving traffic)."""
    from dynamo_tpu.runtime.telemetry import SloConfig

    service = HttpService(
        ModelManager(), host="127.0.0.1", port=0,
        slo=SloConfig(ttft_ms=100.0, tpot_ms=20.0),
    )
    model = "hygiene-model"
    service._m_requests(model, "200").inc()
    service._m_inflight(model).set(1)
    service._m_ttft(model).observe(0.1)
    service._m_itl(model).observe(0.01)
    service._m_duration(model).observe(0.5)
    service._m_queue(model).observe(0.02)
    service._m_output_tokens(model).inc(10)
    service._m_input_tokens(model).inc(20)
    # SLA telemetry path: one attained + one violated request through the
    # real recording helper (digest families + SLO/goodput counters/gauges).
    import time

    t0 = time.monotonic()
    service._record_request_telemetry(model, t0 - 0.05, t0 - 0.04, t0, 8)
    service._record_request_telemetry(model, t0 - 5.0, t0 - 0.1, t0, 8)
    return service.metrics


def aggregator_registry() -> MetricsRegistry:
    """MetricsAggregator's registry fed one full scrape covering every
    gauge and counter key a worker can report, plus a digest payload and a
    tenant-ledger wire so the fleet digest re-exports and the labeled
    per-tenant families render too."""
    from dynamo_tpu.metrics_aggregator import DIGEST_KEYS
    from dynamo_tpu.runtime.ledger import RequestBill, TenantLedger
    from dynamo_tpu.runtime.telemetry import SloConfig, Telemetry

    telem = Telemetry()
    for name in DIGEST_KEYS:
        telem.observe(name, 0.1)
    ledger = TenantLedger(top_k=4, slo=SloConfig(ttft_ms=100.0, tpot_ms=10.0))
    ledger.record(RequestBill(tenant="hygiene", prefill_device_s=0.1,
                              decode_device_s=0.2, kv_block_s=1.0, queue_s=0.01,
                              output_tokens=8, ttft_s=0.05, tpot_s=0.2))
    agg = MetricsAggregator(drt=None, namespace="ns", component="backend", endpoint="generate")
    stats = {0xA: {**{key: 1.0 for key in GAUGE_KEYS + COUNTER_KEYS},
                   "digests": telem.to_wire(),
                   "tenant_ledger": ledger.to_wire()}}
    agg.export_stats(stats)
    agg.export_stats(stats)  # second scrape exercises the delta path
    return agg.registry


@pytest.mark.parametrize("make_registry", [frontend_registry, aggregator_registry],
                         ids=["frontend", "aggregator"])
def test_registry_hygiene(make_registry):
    registry = make_registry()
    text = registry.render().decode()
    fams = parse_families(text)
    assert fams, "registry rendered no metric families"

    # No duplicate family names (TYPE declared once per family).
    names = re.findall(r"# TYPE (\S+) ", text)
    assert len(names) == len(set(names)), f"duplicate families: {sorted(names)}"

    for name, fam in fams.items():
        # Counters must expose rate()-able *_total samples.
        if fam["type"] == "counter":
            totals = [s for s in fam["samples"] if s.endswith("_total")]
            assert totals, f"counter {name} renders no _total sample"
        # Histograms must declare buckets explicitly — the prometheus_client
        # defaults are request-latency-shaped for generic web apps, not for
        # TTFT/ITL/step-time scales.
        if fam["type"] == "histogram":
            assert fam["le"], f"histogram {name} has no buckets"
            assert fam["le"] != _DEFAULT_LE, (
                f"histogram {name} uses prometheus_client default buckets; "
                "declare buckets= explicitly"
            )


def test_monotonic_worker_stats_export_as_counters():
    """Satellite regression: ``*_total`` worker stats must not be exported
    as Gauges (breaks PromQL rate())."""
    text = aggregator_registry().render().decode()
    fams = parse_families(text)
    for key in COUNTER_KEYS:
        # The classic text format renders counter families WITH the _total
        # suffix, whatever the declared name was.
        fam_name = f"dynamo_component_worker_{key}"
        if not fam_name.endswith("_total"):
            fam_name += "_total"
        assert fams.get(fam_name, {}).get("type") == "counter", (
            f"{key} must export as a Counter, got {fams.get(fam_name)}"
        )


def test_counter_delta_and_restart_semantics():
    agg = MetricsAggregator(drt=None, namespace="ns", component="backend", endpoint="generate")
    agg.export_stats({1: {"mixed_steps_total": 10}})
    agg.export_stats({1: {"mixed_steps_total": 14}})   # +4
    agg.export_stats({1: {"mixed_steps_total": 3}})    # restart → +3
    text = agg.registry.render().decode()
    line = next(l for l in text.splitlines()
                if l.startswith("dynamo_component_worker_mixed_steps_total{"))
    assert line.endswith(" 17.0"), line


def test_decode_host_gap_metrics_render_in_all_roles():
    """The decode host-gap histogram must flow engine → stats → aggregator
    → Prometheus: keys declared in COUNTER_KEYS, emitted by the flight
    recorder's wire dict, and rendered as rate()-able counters."""
    from dynamo_tpu.engine.flight_recorder import GAP_BUCKETS, FlightRecorder

    new_keys = ("decode_host_gap_events_total", "decode_host_gap_seconds_total")
    for key in new_keys:
        assert key in COUNTER_KEYS, f"{key} missing from aggregator COUNTER_KEYS"

    # Flight recorder emits the gap histogram's sum/count counters...
    fr = FlightRecorder()
    fr.record_host_gap(0.003)
    stats = fr.to_stats()
    assert stats["decode_host_gap_events_total"] == 1
    assert stats["decode_host_gap_seconds_total"] > 0
    # ...and the full histogram uses gap-scale buckets (sub-ms floor), not
    # the request-latency defaults.
    buckets, counts = fr.histogram("host_gap")
    assert buckets == GAP_BUCKETS and buckets[0] <= 0.0005
    assert len(counts) == len(buckets) + 1 and sum(counts) == 1
    assert fr.gap_percentile(0.5) <= 0.005 <= fr.gap_percentile(0.99) * 10

    # Aggregator renders them as Counter families (rate()-able).
    fams = parse_families(aggregator_registry().render().decode())
    for key in new_keys:
        assert fams.get(f"dynamo_component_worker_{key}", {}).get("type") == "counter", (
            f"{key} not rendered as a counter by the aggregator"
        )


def test_prefix_cache_metrics_render_in_all_roles():
    """Automatic prefix caching's counters must flow engine/mocker stats →
    aggregator → Prometheus: keys declared in COUNTER_KEYS, present on the
    ForwardPassMetrics wire and the mocker's scrape dict, and rendered as
    rate()-able counters."""
    from dynamo_tpu.engine.kv_cache import BlockAllocator
    from dynamo_tpu.engine.scheduler import ForwardPassMetrics
    from dynamo_tpu.llm.mocker import MockTpuEngine
    from dynamo_tpu.llm.tokens import compute_block_hashes

    new_keys = (
        "cached_tokens_total", "prefix_hit_blocks_total",
        "prefix_miss_blocks_total", "prefix_evicted_blocks_total",
        "prefix_onboard_total",
    )
    for key in new_keys:
        assert key in COUNTER_KEYS, f"{key} missing from aggregator COUNTER_KEYS"

    # Wire shape: the scheduler's metrics snapshot carries every key.
    wire = ForwardPassMetrics().to_wire()
    for key in new_keys:
        assert key in wire, f"{key} missing from ForwardPassMetrics wire"

    # Allocator ground truth: hit/miss/evict counters move with the cache.
    alloc = BlockAllocator(4)
    tokens = list(range(32))
    hashes = compute_block_hashes(tokens, 16)
    blocks = alloc.allocate(2)
    alloc.register_hashes(blocks, hashes)
    alloc.release(blocks)
    assert alloc.match_prefix(hashes) == blocks  # hit both
    alloc.release(blocks)
    assert alloc.match_prefix([123456789]) == []  # miss
    assert alloc.hit_blocks_total == 2 and alloc.miss_blocks_total == 1
    alloc.allocate(4)  # forces eviction of the two cached blocks
    assert alloc.evicted_blocks_total == 2

    # Mocker scrape dict exposes the same keys as the real engine's
    # stats_handler (router e2e fleets scrape real hit accounting).
    stats = MockTpuEngine().stats_handler()
    for key in ("cached_tokens_total", "prefix_hit_blocks_total",
                "prefix_miss_blocks_total", "prefix_evicted_blocks_total"):
        assert key in stats, f"{key} missing from mocker stats_handler"

    # Aggregator renders them as Counter families (rate()-able).
    fams = parse_families(aggregator_registry().render().decode())
    for key in new_keys:
        assert fams.get(f"dynamo_component_worker_{key}", {}).get("type") == "counter", (
            f"{key} not rendered as a counter by the aggregator"
        )


def test_tenant_ledger_metrics_render_in_all_roles():
    """Tenant capacity accounting must flow scheduler/mocker →
    stats scrape → aggregator → Prometheus: the flat worker keys are in
    COUNTER_KEYS/GAUGE_KEYS and on the mocker's scrape dict (with the
    nested sketch wire), and the aggregator renders both the worker
    counters and the fleet-merged LABELED per-tenant families."""
    from dynamo_tpu.llm.mocker import MockTpuEngine
    from dynamo_tpu.metrics_aggregator import TENANT_FAMILY_BY_DIM

    flat_counters = (
        "tenant_billed_device_seconds_total", "tenant_billed_kv_block_seconds_total",
        "tenant_billed_queue_seconds_total", "tenant_billed_output_tokens_total",
        "tenant_bills_total", "tenant_slo_attained_total", "tenant_slo_violated_total",
    )
    for key in flat_counters:
        assert key in COUNTER_KEYS, f"{key} missing from aggregator COUNTER_KEYS"
    assert "tenant_tracked" in GAUGE_KEYS

    # Mocker scrape parity: same flat keys + the nested sketch wire the
    # real engine's stats_handler exports.
    stats = MockTpuEngine().stats_handler()
    for key in flat_counters + ("tenant_tracked",):
        assert key in stats, f"{key} missing from mocker stats_handler"
    wire = stats["tenant_ledger"]
    assert set(wire["sketches"]) == {"device_seconds", "kv_block_seconds",
                                     "queue_seconds"}

    # Aggregator: worker counters render rate()-able, and the labeled
    # fleet families carry the tenant label (plus phase for SLO).
    text = aggregator_registry().render().decode()
    fams = parse_families(text)
    for key in flat_counters:
        assert fams.get(f"dynamo_component_worker_{key}", {}).get("type") == "counter", (
            f"{key} not rendered as a counter by the aggregator"
        )
    for fam in set(TENANT_FAMILY_BY_DIM.values()) | {"tenant_slo_attained_total",
                                                     "tenant_slo_violated_total"}:
        assert fams.get(f"dynamo_component_{fam}", {}).get("type") == "counter", (
            f"labeled fleet family {fam} not rendered as a counter"
        )
    assert 'tenant="hygiene"' in text and 'tenant="other"' in text
    # The hygiene bill violates TPOT (200 ms vs a 10 ms target) and attains
    # TTFT — both per-phase labeled samples must render, with the verdict.
    slo_lines = [l for l in text.splitlines()
                 if l.startswith("dynamo_component_tenant_slo_violated_total{")
                 and 'tenant="hygiene"' in l]
    by_phase = {("tpot" if 'phase="tpot"' in l else "ttft"): float(l.rsplit(" ", 1)[1])
                for l in slo_lines}
    assert by_phase == {"ttft": 0.0, "tpot": 1.0}


def test_static_metrics_drift_dtlint_cross_check():
    """The static half of this file's contract, via dtlint MET001: every
    counter emitted on the worker-scrape wire is registered in
    COUNTER_KEYS, every registered key is emitted AND pinned by a Grafana
    panel expr, and the dashboard references no unknown worker keys — so
    this dynamic render test and the MET001 CI gate can never drift apart
    (they read the same key lists and the same dashboard)."""
    import os

    from tools.dtlint import LintConfig, run_lint

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = run_lint(
        LintConfig(root=repo),
        rules=["MET001"],
        baseline_path=os.path.join(repo, "dtlint_baseline.json"),
    )
    assert result.findings == [], (
        "metrics drift (code ↔ COUNTER_KEYS/GAUGE_KEYS ↔ Grafana):\n"
        + "\n".join(f.render() for f in result.findings)
    )
    assert result.stale_baseline == [], result.stale_baseline

    # And the cross-check itself is wired to the same registries this
    # file renders: a key list the aggregator doesn't actually export
    # would fail the dynamic tests above.
    for key in COUNTER_KEYS:
        assert key.endswith("_total"), f"counter key {key} must end _total"


def test_get_or_create_rejects_label_mismatch_on_reuse():
    """Regression: sibling registries reusing a collector with a DIFFERENT
    label set must get a clear error at declaration time, not a confusing
    .labels() blow-up (or silent mis-labelling) later."""
    root = MetricsRegistry()
    root.child(worker="a").gauge("shared_metric", "doc").set(1)
    with pytest.raises(ValueError, match="already registered with labels"):
        root.child(zone="b").gauge("shared_metric", "doc")
    # Same label set from another sibling still reuses cleanly.
    root.child(worker="b").gauge("shared_metric", "doc").set(2)
