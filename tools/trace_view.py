#!/usr/bin/env python
"""Render request traces from a JSONL span export.

The serving stack (frontend/worker ``--trace-file``, or ``DYN_TRACE_FILE``)
writes one JSON record per span/event; this tool turns them into a
per-request timeline — the "where did this request's 242 ms go" view — or a
Chrome-trace file for chrome://tracing / Perfetto.

Usage::

    python tools/trace_view.py trace.jsonl                 # list traces
    python tools/trace_view.py trace.jsonl -t <trace_id>   # one timeline
    python tools/trace_view.py trace.jsonl --request <id>  # one request (alias)
    python tools/trace_view.py trace.jsonl --all           # every timeline
    python tools/trace_view.py trace.jsonl --summary       # digest percentiles
    python tools/trace_view.py trace.jsonl --chrome out.json
    python tools/trace_view.py incident_0001_queue_wait_p99.json   # bundle ring

Multiple input files merge (frontend + worker processes each write their
own file; records carry the trace id, so merging is a concat). Incident
bundles written by ``runtime/incidents.py`` are accepted directly: their
embedded trace ring joins the record set, so the black box of a crashed
or anomalous worker renders with the same timelines as a live export.

Crash-time flight recordings are first-class input: a process dying
mid-write leaves a truncated final line (and possibly records missing
fields) — malformed lines are skipped and incomplete records ignored
rather than poisoning the whole file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.runtime.tracing import chrome_trace, read_trace_file
from dynamo_tpu.runtime.telemetry import LatencyDigest

BAR_WIDTH = 40


def read_records(path: str) -> List[dict]:
    """Records from a JSONL trace file OR an incident bundle (whose
    ``trace_ring`` is the per-process black box at capture time)."""
    try:
        from dynamo_tpu.runtime.incidents import BUNDLE_SCHEMA

        with open(path) as f:
            obj = json.load(f)
        if isinstance(obj, dict) and obj.get("schema") == BUNDLE_SCHEMA:
            return [r for r in obj.get("trace_ring") or [] if isinstance(r, dict)]
    except (OSError, ValueError):
        pass
    return read_trace_file(path)


def group_by_trace(records: List[dict]) -> Dict[str, List[dict]]:
    traces: Dict[str, List[dict]] = defaultdict(list)
    for rec in records:
        # Records must carry a timestamp to be placeable on a timeline;
        # a crash mid-serialization can leave ts-less fragments.
        if (
            rec.get("kind") in ("span", "event")
            and rec.get("trace_id")
            and isinstance(rec.get("ts"), (int, float))
        ):
            traces[rec["trace_id"]].append(rec)
    for recs in traces.values():
        recs.sort(key=lambda r: r.get("ts") or 0.0)
    return traces


# --summary: which record fields carry a duration/latency, keyed by the
# phase name the digest reports under. Spans contribute their dur_s under
# the span name; events map their latency attribute explicitly.
_EVENT_LATENCY_ATTRS = {
    "prefill_chunk": ("prefill_chunk", "dur_s"),
    "mixed_ride": ("mixed_ride", "dur_s"),
    "first_token": ("ttft", "ttft_s"),
    "admitted": ("queue_wait", "queue_s"),
}


def summarize(records: List[dict], out=sys.stdout) -> None:
    """Per-phase digest percentiles over every record in the files: span
    durations by span name plus the scheduler's latency-bearing lifecycle
    events (ttft, queue_wait, chunk/ride durations)."""
    digests: Dict[str, LatencyDigest] = {}

    def observe(key: str, value) -> None:
        if not isinstance(value, (int, float)) or value < 0:
            return
        digests.setdefault(key, LatencyDigest()).observe(float(value))

    for rec in records:
        kind = rec.get("kind")
        name = rec.get("name") or "?"
        if kind == "span":
            observe(f"span:{name}", rec.get("dur_s"))
        elif kind == "event":
            mapped = _EVENT_LATENCY_ATTRS.get(name)
            if mapped is not None:
                key, attr = mapped
                observe(key, (rec.get("attrs") or {}).get(attr))
    if not digests:
        out.write("no latency-bearing records found\n")
        return
    out.write(f"{'phase':<20} {'count':>7} {'p50 ms':>10} {'p90 ms':>10} "
              f"{'p99 ms':>10} {'max ms':>10}\n")
    for key in sorted(digests):
        d = digests[key]
        p50, p90, p99 = d.percentiles((0.5, 0.9, 0.99))
        out.write(
            f"{key:<20} {d.count:>7} {1000 * p50:>10.2f} {1000 * p90:>10.2f} "
            f"{1000 * p99:>10.2f} {1000 * d.max:>10.2f}\n"
        )


def trace_summary(trace_id: str, recs: List[dict]) -> str:
    t0 = min(r["ts"] for r in recs)
    t1 = max(r["ts"] + (r.get("dur_s") or 0.0) for r in recs)
    services = sorted({r.get("service") or "?" for r in recs})
    return (
        f"{trace_id}  {len(recs):3d} records  {1000 * (t1 - t0):8.1f} ms  "
        f"[{', '.join(services)}]"
    )


def render_timeline(trace_id: str, recs: List[dict], out=sys.stdout) -> None:
    t0 = min(r["ts"] for r in recs)
    t1 = max(r["ts"] + (r.get("dur_s") or 0.0) for r in recs)
    total = max(t1 - t0, 1e-9)
    out.write(f"trace {trace_id}  ({1000 * total:.1f} ms total)\n")
    for rec in recs:
        off = rec["ts"] - t0
        dur = rec.get("dur_s") or 0.0
        lo = int(BAR_WIDTH * off / total)
        hi = max(lo + 1, int(BAR_WIDTH * (off + dur) / total)) if dur else lo + 1
        bar = " " * lo + ("█" * (hi - lo) if rec["kind"] == "span" else "·")
        bar = bar[:BAR_WIDTH].ljust(BAR_WIDTH)
        label = f"{rec.get('service') or '?':>10}  {rec.get('name') or '?':<16}"
        timing = f"+{1000 * off:8.2f} ms"
        timing += f"  {1000 * dur:8.2f} ms" if dur else " " * 12
        attrs = rec.get("attrs") or {}
        detail = " ".join(f"{k}={v}" for k, v in attrs.items() if k != "request_id")
        out.write(f"  |{bar}| {label} {timing}  {detail}\n")
        for ev in rec.get("events") or []:
            eoff = (ev.get("ts") or rec["ts"]) - t0
            out.write(f"  |{' ' * BAR_WIDTH}|   {'':>8}· {ev.get('name')} +{1000 * eoff:.2f} ms\n")


def main() -> int:
    p = argparse.ArgumentParser(description="dynamo-tpu trace viewer")
    p.add_argument("files", nargs="+",
                   help="JSONL trace files and/or incident bundles (merged)")
    p.add_argument("-t", "--trace-id", default=None, help="render one trace's timeline")
    p.add_argument("--request", default=None, metavar="TRACE_ID",
                   help="filter the timeline/summary to one request's trace id")
    p.add_argument("--all", action="store_true", help="render every trace's timeline")
    p.add_argument("--summary", action="store_true",
                   help="per-phase digest percentiles across all traces")
    p.add_argument("--chrome", default=None, metavar="OUT",
                   help="write a Chrome-trace/Perfetto JSON file")
    args = p.parse_args()
    if args.request:
        args.trace_id = args.request

    records: List[dict] = []
    for path in args.files:
        records.extend(read_records(path))
    if args.request:
        # --request also scopes --summary/--chrome to the one request.
        records = [r for r in records if r.get("trace_id") == args.request]

    if args.summary:
        summarize(records)
        return 0

    traces = group_by_trace(records)
    if not traces:
        print("no trace records found", file=sys.stderr)
        return 1

    if args.chrome:
        selected = records if args.trace_id is None else traces.get(args.trace_id, [])
        with open(args.chrome, "w") as f:
            json.dump(chrome_trace(selected), f)
        print(f"wrote {args.chrome} ({len(selected)} records)")
        return 0

    if args.trace_id:
        recs = traces.get(args.trace_id)
        if not recs:
            print(f"trace {args.trace_id} not found", file=sys.stderr)
            return 1
        render_timeline(args.trace_id, recs)
        return 0

    if args.all:
        for tid, recs in sorted(traces.items(), key=lambda kv: kv[1][0]["ts"]):
            render_timeline(tid, recs)
            print()
        return 0

    for tid, recs in sorted(traces.items(), key=lambda kv: kv[1][0]["ts"]):
        print(trace_summary(tid, recs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
