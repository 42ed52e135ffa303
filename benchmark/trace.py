"""From a profiler trace to numbers.

``events_from_xplane`` flattens an ``.xplane.pb`` (read with
``jax.profiler.ProfileData``, nothing else) into rows
``[plane, line, name, start_ns, duration_ns]``; every reduction below works on
such rows, so the small trace recorded on the chip and kept under
``fixtures/`` checks them on the CPU. The interval union and the event scan
follow ``runtime/profiling.py::parse_trace_events`` (copied in spirit: device
lanes are the "XLA Ops" lines of device planes; "XLA Modules" and "Steps"
hold enclosing spans that would double-count).

The benchmark's own wrappers write host marks into the same trace with
``jax.profiler.TraceAnnotation`` (names starting ``bench:``), so device
operations, program executions and host spans share one clock.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Row = list  # [plane, line, name, start_ns, duration_ns]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench:"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def events_from_xplane(path: str) -> List[Row]:
    from jax.profiler import ProfileData

    rows: List[Row] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(MARK):
                    continue  # of host threads only the benchmark's marks are kept
                rows.append([plane.name, line.name, name, int(ev.start_ns), int(ev.duration_ns)])
    return rows


def save_rows(path: str, rows: List[Row]) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load_rows(path: str) -> List[Row]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    """Total covered length of a set of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_s is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return total + ((cur_e - cur_s) if cur_s is not None else 0)


def stable_name(name: str) -> str:
    """An operation's name without what changes from build to build. The
    chip's trace names an operation by its whole HLO line
    (``%fusion.163 = bf16[288,14336]{...} fusion(...)``): keep what stands
    before `` = ``, drop the leading ``%`` and the trailing instance numbers."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head or name


def self_times(rows: List[Row], plane: str, t0: int, t1: int) -> List[Tuple[str, int]]:
    """(name, self nanoseconds) of each operation of one device plane inside
    ``[t0, t1)``. Operations nest on a lane (a ``while`` encloses its body's
    operations): an operation's self time is its duration less its children's,
    so the sum over operations is the lane's busy time and nothing counts twice."""
    ops = sorted((r for r in rows if r[0] == plane and r[1] == OPS_LINE and r[3] < t1 and r[3] + r[4] > t0),
                 key=lambda r: (r[3], -r[4]))
    out: List[list] = []
    stack: List[Tuple[int, int]] = []  # (end, index into out)
    for r in ops:
        s, e = max(r[3], t0), min(r[3] + r[4], t1)
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([stable_name(r[2]), e - s])
        stack.append((e, len(out) - 1))
    return [(n, max(d, 0)) for n, d in out]


def device_planes(rows: List[Row]) -> List[str]:
    return sorted({r[0] for r in rows if r[0].startswith("/device:")})


def marks(rows: List[Row]) -> List[Row]:
    return sorted((r for r in rows if r[2].startswith(MARK)), key=lambda r: r[3])


def window_of(rows: List[Row]) -> Tuple[int, int]:
    """The traced window: between the benchmark's ``bench:window`` marks if
    both are there, else from the first to the last device operation."""
    ms = {r[2]: r[3] for r in marks(rows) if r[2] in (MARK + "window_open", MARK + "window_close")}
    if len(ms) == 2:
        return ms[MARK + "window_open"], ms[MARK + "window_close"]
    ops = [r for r in rows if r[1] == OPS_LINE]
    if not ops:
        return 0, 0
    return min(r[3] for r in ops), max(r[3] + r[4] for r in ops)


def busy(rows: List[Row]) -> dict:
    """Seconds in which an operation ran on the device, averaged over the
    device planes, and the window's length."""
    t0, t1 = window_of(rows)
    planes = device_planes(rows)
    if not planes or t1 <= t0:
        return {"busy_s": None, "window_s": None, "chips": 0}
    per = []
    for p in planes:
        iv = [(max(r[3], t0), min(r[3] + r[4], t1)) for r in rows
              if r[0] == p and r[1] == OPS_LINE and r[3] < t1 and r[3] + r[4] > t0]
        per.append(union_ns(iv))
    return {"busy_s": sum(per) / len(per) / 1e9, "window_s": (t1 - t0) / 1e9, "chips": len(planes)}


def top_ops(rows: List[Row], n: int = 10) -> List[list]:
    """The ``n`` operations with the most self time on the first device plane."""
    t0, t1 = window_of(rows)
    planes = device_planes(rows)
    tot: Dict[str, int] = {}
    for name, ns in (self_times(rows, planes[0], t0, t1) if planes else []):
        tot[name] = tot.get(name, 0) + ns
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _host_state_at(ms: List[Row], t: int) -> str:
    """What the host was doing at trace time ``t``, from the benchmark's
    marks: inside a span (``bench:span|<name>`` rows have a duration) the
    innermost one, refined by the last point mark before ``t``."""
    inside, last_point = None, None
    for r in ms:
        if r[3] > t:
            break
        body = r[2][len(MARK):]
        if body.startswith("span|"):
            if r[3] + r[4] >= t:
                inside = body.split("|")[1]
        elif body.startswith(("exec|", "done|")):
            last_point = body.split("|")[0] + ":" + body.split("|")[1]
    if inside is None:
        return "outside_scheduler.step"
    return f"inside_{inside}" + (f">after_{last_point}" if last_point else "")


def idle_gaps(rows: List[Row], n: int = 10) -> List[list]:
    """The ``n`` longest gaps between device operations (first device plane),
    each named by what the host was doing when it began."""
    t0, t1 = window_of(rows)
    planes = device_planes(rows)
    if not planes:
        return []
    iv = sorted((max(r[3], t0), min(r[3] + r[4], t1)) for r in rows
                if r[0] == planes[0] and r[1] == OPS_LINE and r[3] < t1 and r[3] + r[4] > t0)
    gaps, end = [], t0
    for s, e in iv:
        if s > end:
            gaps.append((s - end, end))
        end = max(end, e)
    if t1 > end:
        gaps.append((t1 - end, end))
    ms = marks(rows)
    return [[_host_state_at(ms, start + 1), dur / 1e9] for dur, start in sorted(gaps, reverse=True)[:n]]


def steps(rows: List[Row]) -> List[dict]:
    """One record per program dispatch that the benchmark's wrappers marked:
    the ``exec`` mark (kind, shape key, rows and context tokens of the batch),
    the following ``done`` mark (tokens the step produced), and the device time
    of the operations between the two marks on the first device plane."""
    planes = device_planes(rows)
    ops = sorted((r for r in rows if planes and r[0] == planes[0] and r[1] == OPS_LINE), key=lambda r: r[3])
    starts = [r[3] for r in ops]
    out, open_exec = [], None
    for r in marks(rows):
        body = r[2][len(MARK):].split("|")
        if body[0] == "exec":
            open_exec = {"kind": body[1], "key": body[2], "t_exec": r[3],
                         **{k: float(v) for k, v in (f.split("=") for f in body[3:])}}
        elif body[0] == "done" and open_exec is not None:
            rec = dict(open_exec, phase=body[1], t_done=r[3],
                       **{k: float(v) for k, v in (f.split("=") for f in body[2:])})
            lo, hi = bisect.bisect_left(starts, rec["t_exec"]), bisect.bisect_right(starts, rec["t_done"])
            rec["device_s"] = union_ns((o[3], o[3] + o[4]) for o in ops[lo:hi]) / 1e9
            rec["n_ops"] = hi - lo
            out.append(rec)
            open_exec = None
    t0, t1 = window_of(rows)
    return [s for s in out if s["t_exec"] >= t0 and s["t_done"] <= t1]
