"""Compare two bench rounds section-by-section with regression thresholds.

``bench.py`` emits one JSON object per round; the repo keeps the history as
``BENCH_r*.json`` wrappers (``{n, cmd, rc, tail, parsed}``). A fresh round is
only a number until it's placed against the previous one — and eyeballing
two 2000-char JSON blobs is how a 15% decode regression ships. This tool
makes the comparison mechanical:

    python tools/bench_diff.py BENCH_r05.json new.json
    python tools/bench_diff.py --latest            # two newest rounds in repo
    python tools/bench_diff.py old.json new.json --strict   # rc=1 on regression

Input tolerance (a diff tool that crashes on the history it must read is
useless): each input may be a raw bench output (``{metric, value, detail}``),
a round wrapper with ``parsed`` set, or a wrapper whose ``parsed`` is null —
there the ``tail`` is scanned for the final JSON line, and failing that, for
intact per-section sub-objects (``"observability": {...}``) recovered with
``raw_decode`` from the truncated fragment. Sections absent on either side
are reported as not-comparable, never as regressions.

Thresholds are per-metric, not global: throughput-style numbers (higher
better) regress on a relative drop, overhead/latency percentages (lower
better) regress on an absolute rise, and invariant booleans (``converged``,
``within_budget``, ``agreement.ok``, 0 post-warmup compiles) regress on any
true→false flip. Improvements are reported, not gated.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

# Sections worth recovering from a truncated tail fragment: every dict the
# bench's ``assemble`` places under detail.
_SECTION_KEYS = (
    "decode_attention", "prefill", "tpu_http_e2e", "http_e2e", "router_prefix",
    "prefix_reuse", "large_model", "mixed_admission", "observability",
    "device_truth", "guided_overhead", "autoscale", "elastic",
)


def _recover_sections(tail: str) -> Dict[str, Any]:
    """Pull intact ``"<section>": {...}`` sub-objects out of a truncated
    output fragment. The fragment's head is usually missing, so the full
    line never parses — but later sections often survive whole."""
    dec = json.JSONDecoder()
    out: Dict[str, Any] = {}
    for key in _SECTION_KEYS:
        for m in re.finditer(r'"%s"\s*:\s*\{' % re.escape(key), tail):
            try:
                obj, _ = dec.raw_decode(tail, m.end() - 1)
            except ValueError:
                continue
            if isinstance(obj, dict):
                out[key] = obj  # last occurrence wins (final summary line)
    # decode_sweep is a list of points.
    for m in re.finditer(r'"decode_sweep"\s*:\s*\[', tail):
        try:
            obj, _ = dec.raw_decode(tail, m.end() - 1)
        except ValueError:
            continue
        if isinstance(obj, list):
            out["decode_sweep"] = obj
    return out


def load_round(path: str) -> Tuple[Dict[str, Any], str]:
    """Returns (bench-result-shaped dict, provenance note). The result
    always has a ``detail`` dict; ``metric``/``value`` may be None when
    only fragments were recoverable."""
    with open(path) as f:
        obj = json.load(f)
    if isinstance(obj, dict) and "detail" in obj:
        return obj, "raw"
    if isinstance(obj, dict) and "parsed" in obj:
        if isinstance(obj.get("parsed"), dict):
            return obj["parsed"], "wrapper"
        tail = obj.get("tail") or ""
        # Newest complete final line, if any line survived whole.
        final = None
        for line in tail.splitlines():
            line = line.strip()
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and "metric" in cand:
                final = cand
        if final is not None:
            return final, "tail-line"
        sections = _recover_sections(tail)
        return {"metric": None, "value": None, "detail": sections}, (
            f"tail-fragment ({len(sections)} sections recovered)"
        )
    raise ValueError(f"{path}: not a bench round (no 'detail' or 'parsed')")


# --------------------------------------------------------------------------
# comparison spec
# --------------------------------------------------------------------------

@dataclass
class Check:
    section: str
    label: str
    path: Tuple[str, ...]          # key path under detail
    direction: str                 # "higher" | "lower" | "flag"
    rel_tol: float = 0.10          # relative drop allowed (higher-better)
    abs_tol: float = 0.0           # absolute rise allowed (lower-better)


CHECKS: List[Check] = [
    Check("observability", "tracing overhead %", ("observability", "overhead_pct"),
          "lower", abs_tol=1.0),
    Check("observability", "within ≤2% budget", ("observability", "within_budget"),
          "flag"),
    Check("observability", "post-warmup compiles = 0",
          ("observability", "compiles_after_warmup"), "lower", abs_tol=0.0),
    Check("guided_overhead", "guided overhead %", ("guided_overhead", "overhead_pct"),
          "lower", abs_tol=1.5),
    Check("prefix_reuse", "prefix-reuse speedup", ("prefix_reuse", "speedup"),
          "higher", rel_tol=0.15),
    Check("autoscale", "SLO attainment", ("autoscale", "slo_attainment"),
          "higher", rel_tol=0.10),
    Check("autoscale", "converged on oracle", ("autoscale", "converged"), "flag"),
    Check("device_truth", "measured/modeled agreement",
          ("device_truth", "agreement", "ok"), "flag"),
    Check("device_truth", "measured-vs-modeled MFU rel err",
          ("device_truth", "agreement", "mfu_rel_err"), "lower", abs_tol=0.02),
    Check("http_e2e", "http e2e tok/s", ("http_e2e", "tok_s"),
          "higher", rel_tol=0.15),
    Check("tpu_http_e2e", "serving tok/s", ("tpu_http_e2e", "tok_s"),
          "higher", rel_tol=0.15),
]


def _dig(detail: Dict[str, Any], path: Tuple[str, ...]) -> Any:
    cur: Any = detail
    for key in path:
        if not isinstance(cur, dict):
            return None
        cur = cur.get(key)
        # autoscale round shape: asserts live under a "summary" sub-dict.
        if cur is None and isinstance(detail.get(path[0]), dict) and key != path[0]:
            parent = detail[path[0]].get("summary")
            if isinstance(parent, dict) and key in parent:
                cur = parent[key]
    return cur


def _decode_points(detail: Dict[str, Any]) -> Dict[Tuple[int, int], float]:
    out: Dict[Tuple[int, int], float] = {}
    for p in detail.get("decode_sweep") or []:
        if isinstance(p, dict) and "batch" in p and "tok_s_per_user" in p:
            out[(p["batch"], p.get("ctx", 0))] = float(p["tok_s_per_user"])
    return out


def compare(old: Dict[str, Any], new: Dict[str, Any]) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    od, nd = old.get("detail") or {}, new.get("detail") or {}

    # Headline metric, when both rounds carry one on the same axis.
    if (old.get("value") is not None and new.get("value") is not None
            and old.get("metric") == new.get("metric")):
        ov, nv = float(old["value"]), float(new["value"])
        drop = (ov - nv) / ov if ov else 0.0
        rows.append({
            "section": "headline", "label": old["metric"], "old": ov, "new": nv,
            "delta_pct": round(100.0 * (nv - ov) / ov, 2) if ov else None,
            "verdict": "regression" if drop > 0.10 else
                       ("improved" if nv > ov else "ok"),
        })

    # Decode sweep: per (batch, ctx) point, 10% relative drop threshold.
    op, np_ = _decode_points(od), _decode_points(nd)
    for key in sorted(set(op) & set(np_)):
        ov, nv = op[key], np_[key]
        drop = (ov - nv) / ov if ov else 0.0
        rows.append({
            "section": "decode_sweep", "label": f"b{key[0]} ctx{key[1]} tok/s/user",
            "old": ov, "new": nv,
            "delta_pct": round(100.0 * (nv - ov) / ov, 2) if ov else None,
            "verdict": "regression" if drop > 0.10 else
                       ("improved" if nv > ov else "ok"),
        })

    for c in CHECKS:
        ov, nv = _dig(od, c.path), _dig(nd, c.path)
        if ov is None or nv is None:
            rows.append({"section": c.section, "label": c.label,
                         "old": ov, "new": nv, "delta_pct": None,
                         "verdict": "not-comparable"})
            continue
        if c.direction == "flag":
            ok_old, ok_new = bool(ov), bool(nv)
            rows.append({"section": c.section, "label": c.label,
                         "old": ok_old, "new": ok_new, "delta_pct": None,
                         "verdict": "regression" if (ok_old and not ok_new)
                         else ("improved" if (not ok_old and ok_new) else "ok")})
            continue
        ov, nv = float(ov), float(nv)
        delta = round(100.0 * (nv - ov) / ov, 2) if ov else None
        if c.direction == "higher":
            drop = (ov - nv) / ov if ov else 0.0
            verdict = ("regression" if drop > c.rel_tol
                       else ("improved" if nv > ov else "ok"))
        else:  # lower-better: absolute rise beyond tolerance regresses
            verdict = ("regression" if nv - ov > c.abs_tol
                       else ("improved" if nv < ov else "ok"))
        rows.append({"section": c.section, "label": c.label, "old": ov,
                     "new": nv, "delta_pct": delta, "verdict": verdict})
    return rows


def _fmt(v: Any) -> str:
    if isinstance(v, bool):
        return "yes" if v else "no"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("rounds", nargs="*", help="OLD.json NEW.json")
    ap.add_argument("--latest", action="store_true",
                    help="compare the two newest BENCH_r*.json in the repo root")
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 if any section regressed")
    args = ap.parse_args(argv)

    if args.latest:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        rounds = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
        if len(rounds) < 2:
            print("bench_diff: fewer than two BENCH_r*.json rounds", file=sys.stderr)
            return 2
        paths = rounds[-2:]
    elif len(args.rounds) == 2:
        paths = args.rounds
    else:
        ap.error("provide OLD.json NEW.json, or --latest")
        return 2

    (old, old_src), (new, new_src) = load_round(paths[0]), load_round(paths[1])
    rows = compare(old, new)
    regressions = [r for r in rows if r["verdict"] == "regression"]

    if args.json:
        print(json.dumps({
            "old": {"path": paths[0], "source": old_src},
            "new": {"path": paths[1], "source": new_src},
            "rows": rows, "regressions": len(regressions),
        }, indent=1))
    else:
        print(f"bench_diff: {os.path.basename(paths[0])} ({old_src}) -> "
              f"{os.path.basename(paths[1])} ({new_src})")
        width = max((len(r["label"]) for r in rows), default=10)
        for r in rows:
            d = f"{r['delta_pct']:+.2f}%" if r["delta_pct"] is not None else "     "
            print(f"  [{r['verdict']:>14}] {r['label']:<{width}}  "
                  f"{_fmt(r['old'])} -> {_fmt(r['new'])}  {d}")
        comparable = [r for r in rows if r["verdict"] != "not-comparable"]
        print(f"  {len(comparable)} comparable, {len(regressions)} regression(s), "
              f"{sum(1 for r in rows if r['verdict'] == 'improved')} improved")
    if regressions and args.strict:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
