"""Reductions that several of a cell's own metric readers share
(``metrics/<name>.py``): the window's step entries from the program's log, and
the self time of the device operations that belong to one layer of the model
as a share of the traced slice's busy time.

This profiler's trace names a device operation by its HLO line and carries no
scope (``jax.named_scope`` does not reach it: my chip run, PR 32, call 1), so
an operation is told by what stands in that line: a kernel's own name, or the
shape of an operand or result that only this layer has, worked out from the
configuration's sizes by the metric's own reader.
"""

from __future__ import annotations

from typing import Optional, Sequence

from benchmark import program_trace, trace as tr

DECODE_KINDS = ("decode", "decode_sample", "decode_multi")


def step_entries(run, kinds: Sequence[str]) -> Optional[list]:
    """Attributes of the window's ``sched.step`` entries of the given kinds
    (the step log: all of the window). A program without the log: None."""
    log = program_trace.step_log(run)
    if log is None:
        return None
    t0, t1 = program_trace._window_ns(run)
    return [attrs for name, a, b, _, attrs in list(log.spans)
            if name == "sched.step" and a >= t0 and b <= t1 and attrs and attrs.get("kind") in kinds]


def op_share_pct(run, wanted: Sequence[str]) -> Optional[float]:
    """Percent of the slice's busy time that is self time of operations whose
    HLO line carries one of ``wanted``. No such operation, or no trace: None."""
    rows, busy = getattr(run, "trace_rows", None), getattr(run, "trace_busy", None)
    if not rows or not busy or not busy.get("busy_s"):
        return None
    planes = tr.device_planes(rows)
    if not planes:
        return None
    # Self times go by name: relabel each operation by whether it is this layer's.
    labelled = [[r[0], r[1], "mine" if any(w in r[2] for w in wanted) else "other", r[3], r[4]]
                for r in rows if r[0] == planes[0] and r[1] == tr.OPS_LINE]
    mine = sum(ns for name, ns in tr.self_times(labelled, planes[0], *tr.window_of(rows)) if name == "mine")
    return None if not mine else 100.0 * mine / 1e9 / busy["busy_s"]
