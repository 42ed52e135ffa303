"""Reductions over what the program itself writes (PR 25): its step log and
its spans and program names in the profiler's trace.

The program's ``runtime/tracing.py`` keeps a bounded in-memory log of
step-phase spans (``(name, t0_ns, t1_ns, step, attrs)`` on
``time.monotonic_ns()``) and one record per finished request, and writes the
same spans into any open profiler session under the prefix ``dyn:``; every
step program is a named function, so the device's "XLA Modules" line reads
``jit_mixed_step(...)``, ``jit_decode_multi_w8(...)``.

Two kinds of reduction, as in ``readers.py``:

- from the log, over the whole window (``source: program_span``):
  ``staged_wait_ms``, ``sched_host_ms``;
- from the traced slice (``source: device_trace``): the device's idle time
  split by what the step thread was doing, device time per named program,
  programs per dispatch, the event loop's busy share. The ``dyn:`` host rows
  are read from the same ``.xplane.pb`` (``trace.events_from_xplane`` keeps
  only the benchmark's own marks), once per run.

A program without the log or the spans (any commit before PR 25) gives
``None`` everywhere: the runner then leaves the metric out of the line.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Dict, List, Optional, Tuple

from benchmark import trace as tr
from benchmark.readers import quantile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_state", "trace")  # where run.py keeps the traced slice

DYN = "dyn:"
PRE = ("sched.plan", "sched.upload", "sched.launch")
POST = ("sched.sample", "sched.emit", "sched.account")
SYNC = "sched.sync"
FRONTEND = ("backend.frame", "http.frame", "engine.deliver")
MODULE = re.compile(r"^jit_(\w+?)(?:\(\d+\))?$")
RUNG = re.compile(r"_w(\d+)$")

HostRow = list  # [plane, line, name, start_ns, duration_ns, {stat: value}]


# --- the step log (in memory, the whole window) ----------------------------------


def step_log(run):
    engine = getattr(getattr(run, "hooks", None), "engine", None)
    flight = getattr(getattr(engine, "scheduler", None), "flight", None)
    return getattr(flight, "log", None)


def _window_ns(run) -> Tuple[int, int]:
    t0, t1 = run.window
    return int(t0 * 1e9), int(t1 * 1e9)


def staged_wait_ms(run, q: float = 0.5) -> Optional[float]:
    """``arrival - enqueued`` of the requests the engine took inside the
    window: how long a request sat staged before ``add_request`` saw it."""
    log = step_log(run)
    if log is None:
        return None
    t0, t1 = run.window
    waits = [r["arrival"] - r["enqueued"] for r in list(log.requests)
             if r.get("enqueued") is not None and t0 <= r["enqueued"] <= t1]
    x = quantile(waits, q)
    return None if x is None else 1e3 * x


def host_ms_per_dispatch(spans: List[tuple], t0: int, t1: int) -> Optional[float]:
    """Mean over the iterations that launched a program of ``sched.step``
    less its ``sched.launch`` and ``sched.sync`` spans (a sync nested in
    ``sched.sample`` included): the host's own work in a step."""
    steps: Dict[int, list] = {}
    waits: Dict[int, int] = {}
    launched = set()
    for name, a, b, step, _ in spans:
        if a < t0 or b > t1:
            continue
        if name == "sched.step":
            steps[step] = [a, b]
        elif name in ("sched.launch", SYNC):
            waits[step] = waits.get(step, 0) + (b - a)
            if name == "sched.launch":
                launched.add(step)
    host = [(steps[s][1] - steps[s][0]) - waits.get(s, 0) for s in launched if s in steps]
    return None if not host else sum(host) / len(host) / 1e6


def sched_host_ms(run) -> Optional[float]:
    log = step_log(run)
    return None if log is None else host_ms_per_dispatch(list(log.spans), *_window_ns(run))


# --- the traced slice ---------------------------------------------------------------


def host_rows_from_xplane(path: str) -> List[HostRow]:
    """The program's own spans on the host planes of a trace."""
    from jax.profiler import ProfileData

    rows: List[HostRow] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(DYN):
                    rows.append([plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns),
                                 {str(k): v for k, v in ev.stats}])
    return rows


def split_rows(rows: list) -> Tuple[List[tr.Row], List[HostRow]]:
    """A recorded trace keeps both kinds of row in one list (``trace.save_rows``):
    the program's ``dyn:`` host rows carry their attributes as a sixth element."""
    dyn = [r for r in rows if r[2].startswith(DYN)]
    return [r for r in rows if not r[2].startswith(DYN)], dyn


def dyn_rows(run) -> List[HostRow]:
    """``dyn:`` host rows of the run's traced slice, read once."""
    cached = getattr(run, "_dyn_rows", None)
    if cached is None:
        cached = []
        if getattr(run, "trace_rows", None):
            path = tr.find_xplane(TRACE_DIR)
            if path is not None:
                cached = host_rows_from_xplane(path)
        run._dyn_rows = cached
    return cached


def _named(dyn: List[HostRow], names) -> List[HostRow]:
    want = {DYN + n for n in names}
    return [r for r in dyn if r[2] in want]


def device_gaps(rows: List[tr.Row], t0: int, t1: int) -> List[Tuple[int, int]]:
    """Intervals of ``[t0, t1)`` in which no operation runs on the first device plane."""
    planes = tr.device_planes(rows)
    if not planes or t1 <= t0:
        return []
    iv = sorted((max(r[3], t0), min(r[3] + r[4], t1)) for r in rows
                if r[0] == planes[0] and r[1] == tr.OPS_LINE and r[3] < t1 and r[3] + r[4] > t0)
    gaps, end = [], t0
    for s, e in iv:
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if t1 > end:
        gaps.append((end, t1))
    return gaps


def idle_split(rows: List[tr.Row], dyn: List[HostRow]) -> Optional[dict]:
    """The device's idle nanoseconds of the traced slice, split by what the
    step thread was doing: ``pre`` (inside ``sched.plan`` / ``upload`` /
    ``launch``), ``sync`` (inside ``sched.sync``: launch latency and the
    read-back, the host can do nothing about it there), ``post`` (the host
    part of ``sched.sample``, ``sched.emit``, ``sched.account``), ``loop``
    (outside any ``sched.step``: thread hop, staging, delivery, the frontend
    holding the GIL). The innermost span decides; time inside a step that no
    phase covers counts as ``pre`` before the step's first launch and as
    ``post`` after it. The four parts sum to ``idle``."""
    steps = sorted(_named(dyn, ("sched.step",)), key=lambda r: r[3])
    if not steps or not tr.device_planes(rows):
        return None
    t0, t1 = tr.window_of(rows)
    gaps = device_gaps(rows, t0, t1)
    starts = [r[3] for r in steps]
    phases: Dict[int, List[HostRow]] = {}
    for r in _named(dyn, PRE + POST + (SYNC,)):
        phases.setdefault(r[5].get("step"), []).append(r)
    cuts = sorted({p for r in steps + [x for v in phases.values() for x in v] for p in (r[3], r[3] + r[4])})

    def state(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t >= steps[i][3] + steps[i][4]:
            return "loop"
        kids = phases.get(steps[i][5].get("step"), [])
        inside = [k for k in kids if k[3] <= t < k[3] + k[4]]
        if inside:
            name = min(inside, key=lambda k: k[4])[2][len(DYN):]
            return "sync" if name == SYNC else "pre" if name in PRE else "post"
        launches = [k[3] for k in kids if k[2] == DYN + "sched.launch"]
        return "pre" if not launches or t < min(launches) else "post"

    out = {"pre": 0, "post": 0, "loop": 0, "sync": 0}
    for a, b in gaps:
        lo, hi = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
        edges = [a] + cuts[lo:hi] + [b]
        for x, y in zip(edges, edges[1:]):
            if y > x:
                out[state((x + y) // 2)] += y - x
    out["idle"] = sum(b - a for a, b in gaps)
    out["window"] = t1 - t0
    return out


def idle_pct(run, part: str) -> Optional[float]:
    rows = getattr(run, "trace_rows", None)
    if not rows:
        return None
    split = getattr(run, "_idle_split", None)
    if split is None:
        split = run._idle_split = idle_split(rows, dyn_rows(run)) or {}
    if not split or not split["window"]:
        return None
    return 100.0 * split[part] / split["window"]


def modules(rows: List[tr.Row]) -> List[Tuple[str, int, int]]:
    """(program name, start, duration) of every "XLA Modules" event that lies
    inside the traced slice, first device plane; ``jit_decode_multi_w8(123)``
    reads ``decode_multi_w8``."""
    planes = tr.device_planes(rows)
    t0, t1 = tr.window_of(rows)
    out = []
    for r in rows:
        if planes and r[0] == planes[0] and r[1] == tr.MODULES_LINE and r[3] >= t0 and r[3] + r[4] <= t1:
            m = MODULE.match(r[2])
            out.append((m.group(1) if m else r[2], r[3], r[4]))
    return out


def rung(name: str) -> int:
    """Decode steps one execution of the named program runs: the window rung
    in ``decode_multi_w8`` / ``decode_fused_sampled_w16``, else 1."""
    m = RUNG.search(name)
    return int(m.group(1)) if m else 1


def program_ms(rows: List[tr.Row], prefix: str, per_step: bool) -> Optional[float]:
    """Device milliseconds of the programs whose name starts with ``prefix``:
    per execution, or per decode step of the name's rung."""
    mine = [(n, d) for n, _, d in modules(rows) if n.startswith(prefix)]
    n = sum(rung(name) for name, _ in mine) if per_step else len(mine)
    return None if not n else sum(d for _, d in mine) / n / 1e6


def run_program_ms(run, prefix: str, per_step: bool) -> Optional[float]:
    rows = getattr(run, "trace_rows", None)
    return None if not rows else program_ms(rows, prefix, per_step)


def programs_per_dispatch_of(rows: List[tr.Row], dyn: List[HostRow]) -> Optional[float]:
    """Device programs executed in the traced slice over the scheduler
    iterations in it that launched one: every eager helper (a slice, a
    ``fold_in``, a dtype conversion, the sampler) is a program of its own."""
    t0, t1 = tr.window_of(rows)
    inside = lambda r: r[3] >= t0 and r[3] + r[4] <= t1  # noqa: E731
    steps = {r[5].get("step") for r in _named(dyn, ("sched.step",)) if inside(r)}
    launched = {r[5].get("step") for r in _named(dyn, ("sched.launch",)) if inside(r)} & steps
    if not launched or not tr.device_planes(rows):
        return None
    return len(modules(rows)) / len(launched)


def programs_per_dispatch(run) -> Optional[float]:
    rows = getattr(run, "trace_rows", None)
    return None if not rows else programs_per_dispatch_of(rows, dyn_rows(run))


def frontend_busy_pct_of(rows: List[tr.Row], dyn: List[HostRow]) -> Optional[float]:
    """Share of the traced slice in which the event loop is inside
    ``backend.frame``, ``http.frame`` or ``engine.deliver``: Python the step
    thread's next dispatch has to wait behind."""
    mine = _named(dyn, FRONTEND)
    t0, t1 = tr.window_of(rows)
    if not mine or t1 <= t0:
        return None
    iv = [(max(r[3], t0), min(r[3] + r[4], t1)) for r in mine if r[3] < t1 and r[3] + r[4] > t0]
    return 100.0 * tr.union_ns(iv) / (t1 - t0)


def frontend_busy_pct(run) -> Optional[float]:
    rows = getattr(run, "trace_rows", None)
    return None if not rows else frontend_busy_pct_of(rows, dyn_rows(run))
