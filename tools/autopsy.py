#!/usr/bin/env python
"""Incident / request autopsy: join traces, step rings, and digests into a
"why was this slow" attribution report.

Two modes over the same evidence:

- **Incident (window) mode** — given an incident bundle written by
  ``runtime/incidents.py``, rank every detector signal by how far it sits
  above the baseline it was judged against and attribute the incident to
  the slow-path component with the strongest evidence (queue wait vs
  prefill vs decode vs host gap vs mid-traffic compile vs stall), with the
  digest windows and the recent-step ring as supporting exhibits.
- **Request mode** (``--request <trace-id>``) — given trace records (JSONL
  files and/or a bundle's trace ring), reconstruct one request's phase
  breakdown from its lifecycle events (queued → admitted → first_token →
  finish) and report where its time went, what interfered (preemptions,
  disagg KV hops, mixed-step rides), and — when digests are available —
  where each phase sits against the fleet percentiles.

Usage::

    python tools/autopsy.py incident_0001_queue_wait_p99.json
    python tools/autopsy.py trace.jsonl --request <trace-id>
    python tools/autopsy.py incident_0001_*.json --request <trace-id> --json

Bundles and JSONL files mix freely on the command line; bundle trace rings
and file records merge into one record set.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.runtime.incidents import BUNDLE_SCHEMA
from dynamo_tpu.runtime.telemetry import LatencyDigest
from dynamo_tpu.runtime.tracing import read_trace_file

# Detector signal → the slow-path component it is evidence for.
SIGNAL_PHASE = {
    "queue_wait_p99": "queue_wait",
    "ttft_p99": "prefill",
    "tpot_p99": "decode",
    "host_gap": "decode_host_gap",
    "post_warmup_compile": "compile",
    "engine_stall": "stall",
}


# --- input loading -----------------------------------------------------------

def load_bundle(path: str) -> Optional[dict]:
    """Parse ``path`` as an incident bundle; None when it is not one (a
    JSONL trace file, a truncated write, ...)."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(obj, dict) and obj.get("schema") == BUNDLE_SCHEMA:
        return obj
    return None


def load_inputs(paths: List[str]) -> Tuple[List[dict], List[dict]]:
    """(bundles, trace_records) from a mixed list of bundle and JSONL
    paths. Bundle trace rings fold into the record set."""
    bundles: List[dict] = []
    records: List[dict] = []
    for path in paths:
        bundle = load_bundle(path)
        if bundle is not None:
            bundles.append(bundle)
            records.extend(r for r in bundle.get("trace_ring") or [] if isinstance(r, dict))
        else:
            records.extend(read_trace_file(path))
    return bundles, records


def _digest(bundle: Optional[dict], name: str) -> Optional[LatencyDigest]:
    """The bundle's WINDOW digest for one stream (the distribution at
    capture time), or None."""
    if bundle is None:
        return None
    wire = ((bundle.get("stats") or {}).get("digests") or {}).get(name)
    if not isinstance(wire, dict) or "window" not in wire:
        return None
    try:
        return LatencyDigest.from_wire(wire["window"])
    except (TypeError, ValueError, KeyError):
        return None


# --- incident (window) attribution -------------------------------------------

def incident_report(bundle: dict) -> dict:
    """Attribute one incident bundle to a slow-path component.

    Discrete signals (a mid-traffic compile, a stall transition) are
    categorical evidence and win outright when they fired. Continuous
    signals rank by ``value / baseline`` — how far the signal sits above
    the trailing normal the detector was tracking — so a 1500× queue-wait
    excursion beats the 80× TTFT jump it caused downstream."""
    detector = bundle.get("detector") or {}
    values: Dict[str, float] = detector.get("last_values") or {}
    baselines: Dict[str, float] = detector.get("baselines") or {}
    stats = bundle.get("stats") or {}
    reason = bundle.get("reason") or "?"

    ratios: Dict[str, float] = {}
    for signal in ("queue_wait_p99", "ttft_p99", "tpot_p99", "host_gap"):
        v, b = values.get(signal), baselines.get(signal)
        if v is None or b is None or b <= 0:
            continue
        ratios[signal] = v / b

    evidence: List[str] = []
    if reason == "engine_stall" or float(stats.get("engine_stalled", 0.0) or 0.0):
        attribution = "stall"
        evidence.append("stall watchdog: step loop wedged with work queued")
    elif reason == "post_warmup_compile":
        attribution = "compile"
        evidence.append(
            f"XLA compiled mid-traffic: compiles_after_warmup_total="
            f"{stats.get('compiles_after_warmup_total')}"
        )
    elif ratios:
        top = max(ratios, key=lambda s: ratios[s])
        attribution = SIGNAL_PHASE[top]
        for s in sorted(ratios, key=lambda s: -ratios[s]):
            evidence.append(
                f"{s}: {values[s] * 1000:.2f} ms vs baseline "
                f"{baselines[s] * 1000:.2f} ms ({ratios[s]:.1f}x)"
            )
    else:
        attribution = SIGNAL_PHASE.get(reason, reason)
        evidence.append("no continuous-signal evidence in bundle; attributed by trigger reason")

    # Supporting exhibits: digest percentiles + step-ring summary.
    digests = {}
    for name in ("queue_wait", "ttft", "tpot", "prefill_step", "decode_step", "mixed_step"):
        d = _digest(bundle, name)
        if d is not None and d.count:
            p50, p99 = d.quantile(0.5), d.quantile(0.99)
            digests[name] = {
                "count": d.count,
                "p50_ms": round(1000 * p50, 3),
                "p99_ms": round(1000 * p99, 3),
                "max_ms": round(1000 * d.max, 3),
            }
    flight = bundle.get("flight") or {}
    steps = flight.get("recent_steps") or []
    phases: Dict[str, int] = {}
    for s in steps:
        phases[s.get("phase", "?")] = phases.get(s.get("phase", "?"), 0) + 1

    return {
        "mode": "incident",
        "reason": reason,
        "ts": bundle.get("ts"),
        "detail": bundle.get("detail"),
        "attribution": attribution,
        "signal_ratios": {k: round(v, 2) for k, v in sorted(ratios.items(), key=lambda kv: -kv[1])},
        "evidence": evidence,
        "digests": digests,
        "recent_steps": {
            "count": len(steps),
            "by_phase": phases,
            "host_gap_p99_ms": round(1000 * float((flight.get("host_gap") or {}).get("p99_s") or 0.0), 3),
        },
        "compiles_after_warmup": stats.get("compiles_after_warmup_total"),
        "running": len((bundle.get("debug_state") or {}).get("running") or []),
        "waiting": len((bundle.get("debug_state") or {}).get("waiting") or []),
    }


# --- request attribution ------------------------------------------------------

def request_report(records: List[dict], trace_id: str,
                   bundle: Optional[dict] = None) -> dict:
    """Phase breakdown + attribution for one request's trace records."""
    recs = [r for r in records if r.get("trace_id") == trace_id
            and isinstance(r.get("ts"), (int, float))]
    if not recs:
        return {"mode": "request", "trace_id": trace_id,
                "error": "no records for this trace id"}
    recs.sort(key=lambda r: r["ts"])

    def first_event(name: str) -> Optional[dict]:
        return next((r for r in recs if r.get("name") == name), None)

    def attr(rec: Optional[dict], key: str):
        return (rec or {}).get("attrs", {}).get(key)

    queued = first_event("queued")
    first_token = first_event("first_token")
    finish = first_event("finish")
    t0 = recs[0]["ts"]
    t1 = max(r["ts"] + (r.get("dur_s") or 0.0) for r in recs)

    phases: Dict[str, float] = {}
    ttft_s = attr(first_token, "ttft_s")
    queue_s = attr(first_event("admitted"), "queue_s")
    if queue_s is None and queued is not None and first_event("admitted") is not None:
        queue_s = max(0.0, first_event("admitted")["ts"] - queued["ts"])
    if queue_s is not None:
        phases["queue_wait"] = float(queue_s)
    if ttft_s is not None:
        phases["prefill"] = max(0.0, float(ttft_s) - float(queue_s or 0.0))
    elif first_token is not None and queued is not None:
        phases["prefill"] = max(
            0.0, first_token["ts"] - queued["ts"] - float(queue_s or 0.0)
        )
    if finish is not None and first_token is not None:
        phases["decode"] = max(0.0, finish["ts"] - first_token["ts"])

    # Interference modifiers: not wall-time phases, but the "what else
    # happened to this request" column of the report.
    modifiers: List[str] = []
    preemptions = attr(finish, "preemptions")
    if preemptions:
        modifiers.append(f"preempted {preemptions}x (KV recomputed on resume)")
    n_disagg = sum(1 for r in recs if "disagg" in (r.get("name") or ""))
    if n_disagg:
        modifiers.append(f"disagg KV hop ({n_disagg} transfer events)")
    n_rides = sum(1 for r in recs if r.get("name") == "mixed_ride")
    if n_rides:
        modifiers.append(f"prefill rode {n_rides} mixed decode steps")
    cached = attr(first_token, "cached_tokens")
    if cached:
        modifiers.append(f"{cached} prompt tokens served from prefix cache")

    total = sum(phases.values()) or max(t1 - t0, 1e-9)
    attribution = max(phases, key=lambda p: phases[p]) if phases else "unknown"

    # Fleet context: where does this request sit in the capture-time
    # distribution of each phase?
    fleet: Dict[str, str] = {}
    for name, value in (("queue_wait", queue_s), ("ttft", ttft_s)):
        d = _digest(bundle, name)
        if d is not None and d.count and value is not None:
            fleet[name] = f"p{100.0 * d.rank(float(value)):.1f} of {d.count} in window"

    return {
        "mode": "request",
        "trace_id": trace_id,
        "records": len(recs),
        "total_ms": round(1000 * (t1 - t0), 3),
        "attribution": attribution,
        "phases_ms": {k: round(1000 * v, 3) for k, v in phases.items()},
        "phase_shares": {k: round(v / total, 4) for k, v in phases.items()},
        "modifiers": modifiers,
        "fleet_context": fleet,
        "finish_reason": attr(finish, "reason"),
        "output_tokens": attr(finish, "output_tokens"),
    }


# --- tenant attribution -------------------------------------------------------

# Incident attribution → the ledger dimension that explains "who did it".
_TENANT_DIMENSION = {
    "queue_wait": "queue_seconds",
    "prefill": "device_seconds",
    "decode": "device_seconds",
    "decode_host_gap": "device_seconds",
    "compile": "device_seconds",
    "stall": "device_seconds",
}


def tenant_report(bundle: dict) -> dict:
    """Attribute an incident to tenants: join the bundle's tenant-ledger
    evidence (runtime/ledger.py snapshot) with the window attribution, so
    the report can say e.g. "queue_wait spike is 84% tenant X"."""
    ledger = (bundle.get("evidence") or {}).get("tenant_ledger")
    if not isinstance(ledger, dict) or "device_seconds" not in ledger:
        # Older bundles (or a dead probe): fall back to the raw sketch wire
        # riding the captured stats scrape.
        wire = (bundle.get("stats") or {}).get("tenant_ledger")
        if isinstance(wire, dict):
            from dynamo_tpu.runtime.ledger import attribute

            ledger = attribute(wire)
        else:
            return {"mode": "tenant",
                    "error": "bundle carries no tenant ledger evidence"}

    base = incident_report(bundle)
    dim = _TENANT_DIMENSION.get(base["attribution"], "device_seconds")
    ranked = (ledger.get(dim) or {}).get("tenants") or []
    headline = None
    if ranked:
        top = ranked[0]
        headline = (f"{base['reason']}: {dim.replace('_', ' ')} is "
                    f"{100 * top['share']:.0f}% tenant '{top['tenant']}'")
    return {
        "mode": "tenant",
        "reason": base["reason"],
        "ts": bundle.get("ts"),
        "attribution": base["attribution"],
        "dimension": dim,
        "headline": headline,
        "bills": ledger.get("bills"),
        "ledger": {k: ledger.get(k) for k in
                   ("device_seconds", "kv_block_seconds", "queue_seconds")},
        "slo": ledger.get("slo") or {},
    }


# --- rendering ---------------------------------------------------------------

def render(report: dict, out=sys.stdout) -> None:
    mode = report.get("mode")
    if report.get("error"):
        out.write(f"autopsy: {report['error']}\n")
        return
    if mode == "tenant":
        out.write(f"incident: {report['reason']}  (ts {report.get('ts')})\n")
        out.write(f"attribution: {report['attribution'].upper()} "
                  f"→ ledger dimension {report['dimension']}\n")
        if report.get("headline"):
            out.write(f"  {report['headline']}\n")
        for dim, d in (report.get("ledger") or {}).items():
            if not isinstance(d, dict):
                continue
            out.write(f"{dim} (total {d.get('total', 0.0):.3f}, "
                      f"{report.get('bills', 0)} bills):\n")
            for row in d.get("tenants") or []:
                out.write(f"  {row['tenant']:<24} {row['value']:>12.4f} "
                          f"{100 * row['share']:>6.1f}%  (±{row['error']:.4f})\n")
            out.write(f"  {'<other>':<24} {d.get('other', 0.0):>12.4f} "
                      f"{100 * d.get('other_share', 0.0):>6.1f}%\n")
        for tenant, counts in (report.get("slo") or {}).items():
            v = counts.get("violated") or {}
            a = counts.get("attained") or {}
            out.write(f"slo {tenant}: ttft {a.get('ttft', 0)}/{a.get('ttft', 0) + v.get('ttft', 0)} "
                      f"attained, tpot {a.get('tpot', 0)}/{a.get('tpot', 0) + v.get('tpot', 0)} attained\n")
        return
    if mode == "incident":
        out.write(f"incident: {report['reason']}  (ts {report.get('ts')})\n")
        out.write(f"attribution: {report['attribution'].upper()}\n")
        for line in report.get("evidence") or []:
            out.write(f"  - {line}\n")
        if report.get("digests"):
            out.write(f"{'window digest':<16} {'count':>7} {'p50 ms':>10} {'p99 ms':>10} {'max ms':>10}\n")
            for name, d in report["digests"].items():
                out.write(f"{name:<16} {d['count']:>7} {d['p50_ms']:>10.2f} "
                          f"{d['p99_ms']:>10.2f} {d['max_ms']:>10.2f}\n")
        rs = report.get("recent_steps") or {}
        out.write(f"recent steps: {rs.get('count', 0)} {rs.get('by_phase', {})}  "
                  f"host-gap p99 {rs.get('host_gap_p99_ms', 0)} ms\n")
        out.write(f"engine at capture: {report.get('running')} running / "
                  f"{report.get('waiting')} waiting, "
                  f"compiles_after_warmup={report.get('compiles_after_warmup')}\n")
        return
    out.write(f"request {report['trace_id']}  ({report['total_ms']:.1f} ms total, "
              f"{report['records']} records)\n")
    out.write(f"attribution: {report['attribution'].upper()}\n")
    for name, ms in (report.get("phases_ms") or {}).items():
        share = (report.get("phase_shares") or {}).get(name, 0.0)
        ctx = (report.get("fleet_context") or {}).get(
            "queue_wait" if name == "queue_wait" else "ttft" if name == "prefill" else "", ""
        )
        out.write(f"  {name:<16} {ms:>10.2f} ms  {100 * share:>5.1f}%  {ctx}\n")
    for m in report.get("modifiers") or []:
        out.write(f"  * {m}\n")
    if report.get("finish_reason"):
        out.write(f"finished: {report['finish_reason']} "
                  f"({report.get('output_tokens')} output tokens)\n")


def main() -> int:
    p = argparse.ArgumentParser(description="dynamo-tpu incident/request autopsy")
    p.add_argument("files", nargs="+",
                   help="incident bundle JSON files and/or JSONL trace files (merged)")
    p.add_argument("--request", default=None, metavar="TRACE_ID",
                   help="attribute one request instead of the incident window")
    p.add_argument("--tenant", action="store_true",
                   help="attribute the incident to tenants (capacity-ledger "
                        "evidence: who consumed the device/KV/queue seconds)")
    p.add_argument("--json", action="store_true", help="emit the report as JSON")
    args = p.parse_args()

    bundles, records = load_inputs(args.files)
    bundle = bundles[0] if bundles else None

    if args.request:
        report = request_report(records, args.request, bundle=bundle)
    elif args.tenant:
        if bundle is None:
            print("--tenant needs an incident bundle", file=sys.stderr)
            return 2
        report = tenant_report(bundle)
    elif bundle is not None:
        report = incident_report(bundle)
    else:
        print("no incident bundle given and no --request trace id", file=sys.stderr)
        return 2

    if args.json:
        json.dump(report, sys.stdout, indent=2, default=str)
        print()
    else:
        render(report)
    return 0 if not report.get("error") else 1


if __name__ == "__main__":
    sys.exit(main())
