"""The reductions that metric files name.

A metric is one file ``metrics/<name>.json``: ``{"name", "unit", "better",
"source", "layer", "moves", "reader", "args"}``. Which cells report it is the
manifest's to say (``workloads`` of the entry of the same name in
``BENCHMARK.json``), never the file's. ``reader`` names a function here (or,
for a metric that needs new code, a file ``metrics/<name>.py`` with
``read(run, **args)`` beside the JSON). Each takes the finished run and
returns a number, or ``None`` where it finds nothing to read; the runner then
leaves the metric out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os
import statistics
from typing import Callable, Dict, List, Optional

from benchmark import roofline

HERE = os.path.dirname(os.path.abspath(__file__))
READERS: Dict[str, Callable] = {}


def reader(fn):
    READERS[fn.__name__] = fn
    return fn


def load_metric(name: str) -> dict:
    with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert spec["name"] == name, (spec["name"], name)
    return spec


def read_metric(name: str, run) -> Optional[float]:
    spec = load_metric(name)
    own = os.path.join(HERE, "metrics", f"{name}.py")
    if os.path.exists(own):
        mod_spec = importlib.util.spec_from_file_location(f"benchmark_metric_{abs(hash(name))}", own)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        fn = mod.read
    else:
        fn = READERS[spec["reader"]]
    value = fn(run, **(spec.get("args") or {}))
    return None if value is None else float(value)


def quantile(values: List[float], q: float) -> Optional[float]:
    """The ``q`` quantile, nearest rank from below on sorted values."""
    if not values:
        return None
    v = sorted(values)
    return v[min(len(v) - 1, max(0, int(q * len(v))))]


# --- the client's side (host clock, the load generator's stamps) ----------------


def request_ok(r: dict) -> bool:
    n = sum(f[1] for f in r["frames"])
    u = r.get("usage") or {}
    return (r["error"] is None and r["done"] and r["finish_reason"] == "length"
            and n == r["max_tokens"] == u.get("completion_tokens")
            and u.get("prompt_tokens") == r["prompt_tokens"])


def _counted(run) -> List[dict]:
    return [r for r in run.client["requests"] if r["counted"]]


def _ttft(run, r: dict) -> float:
    if request_ok(r):
        return r["frames"][0][0] - r["due"]
    return run.client["finished"] - r["due"]  # a failed request misses every limit


def _tpot(run, r: dict) -> Optional[float]:
    if not request_ok(r):
        return run.client["finished"] - r["due"]
    n = sum(f[1] for f in r["frames"])
    if n < 2:
        return None
    return (r["frames"][-1][0] - r["frames"][0][0]) / (n - 1)


@reader
def tpot_ms(run, q: float):
    v = [t for t in (_tpot(run, r) for r in _counted(run)) if t is not None]
    x = quantile(v, q)
    return None if x is None else 1e3 * x


@reader
def ttft_ms(run, q: float):
    x = quantile([_ttft(run, r) for r in _counted(run)], q)
    return None if x is None else 1e3 * x


@reader
def out_tok_s(run):
    t0, t1 = run.window
    n = sum(f[1] for r in run.client["requests"] for f in r["frames"] if t0 <= f[0] <= t1)
    return n / (t1 - t0)


@reader
def setup_s(run):
    return run.setup_s


@reader
def loadgen_late_ms(run, q: float):
    late = [r["sent"] - r["due"] for r in _counted(run) if r["sent"] is not None]
    x = quantile(late, q)
    return None if x is None else 1e3 * x


@reader
def frontend_ttft_gap_ms(run):
    """Median over counted requests of (first token at the client) - (first
    token out of the engine's stream, stamped by the benchmark's wrapper)."""
    gaps = []
    for r in _counted(run):
        t_engine = run.hooks.first_token.get(run.prompt_keys.get(r["rid"]))
        if t_engine is not None and request_ok(r):
            gaps.append(r["frames"][0][0] - t_engine)
    return None if not gaps else 1e3 * statistics.median(gaps)


# --- the program's counters and the benchmark's spans ---------------------------


@reader
def telemetry_ms(run, name: str, q: float):
    t0, t1 = run.window
    v = [x for t, n, x in run.hooks.observations if n == name and t0 <= t <= t1]
    x = quantile(v, q)
    return None if x is None else 1e3 * x


@reader
def decode_rows_mean(run):
    """Mean number of running sequences over the decode and mixed dispatches
    inside the window (the scheduler's ``running`` at ``record_exec``)."""
    t0, t1 = run.window
    rows = [r for t, kind, _, r in run.hooks.dispatches
            if t0 <= t <= t1 and kind in ("decode", "decode_multi", "decode_sample", "mixed")]
    return None if not rows else sum(rows) / len(rows)


@reader
def compiles_in_window(run):
    return run.meter.in_window(*run.window)["builds"]


@reader
def compile_s(run):
    s = run.meter.seconds
    return s if s > 0 else None


# --- the device's side (the profiler's trace) ----------------------------------


def _decode_steps(run) -> List[dict]:
    """Pure decode dispatches of the traced window, with steps per dispatch."""
    out = []
    for s in run.trace_steps:
        if s["kind"] == "decode_multi":
            out.append(dict(s, n_steps=int(s["key"].split(",")[0])))
        elif s["kind"] in ("decode", "decode_sample"):
            out.append(dict(s, n_steps=1))
    return [s for s in out if s["device_s"] > 0 and s["rows"] > 0]


@reader
def decode_step_ms(run):
    """Device time of the decode programs over the decode steps they ran."""
    steps = _decode_steps(run)
    n = sum(s["n_steps"] for s in steps)
    return None if not n else 1e3 * sum(s["device_s"] for s in steps) / n


@reader
def step_roofline_pct(run):
    """Least time the chip could take for the decode steps of the traced
    window (the larger of bytes over peak bandwidth and FLOPs over peak
    compute, per step, contexts growing by one token a step; what a step
    needs is counted by the run's family) over their device time."""
    steps = _decode_steps(run)
    if not steps:
        return None
    least = 0.0
    for s in steps:
        w = s["n_steps"]
        ctx_mid = s["ctx"] + s["rows"] * (w - 1) / 2.0
        cost = run.family.decode_step_cost(run.cfg, run.weight_dtype, s["rows"], ctx_mid)
        least += w * roofline.min_seconds(cost, run.device["kind"])["seconds"]
    return 100.0 * least / sum(s["device_s"] for s in steps)


@reader
def prefill_tok_s(run):
    """Prompt tokens computed in the traced slice over the device time of the
    dispatches that carried them: pure prefill and wave dispatches, and mixed
    steps (whose device time also serves the decode rows riding along)."""
    tokens = device = 0.0
    for s in run.trace_steps:
        n = s.get("prefill", s["tokens"]) if s["phase"] == "mixed" else s["tokens"] if s["phase"] in ("prefill", "wave") else 0
        if n and s["device_s"] > 0:
            tokens, device = tokens + n, device + s["device_s"]
    return None if device <= 0 else tokens / device


@reader
def device_idle_pct(run):
    b = run.trace_busy
    if not b or not b.get("window_s"):
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
