"""`step_roofline_pct.reason`: least time the chip could take for the decode dispatches of the traced slice over
the device time of their programs.

What a step needs is the family's `decode_step_cost`, fed from the program's step entries (`dyn:sched.step` rows of
the one trace): `rows`, `ctx`, `experts_visited`, the (layer, expert) pairs the dispatch's rows fell on, and
`skipped_rows`, the (layer, row) pairs that drew the skip choice, both summed over layers and over the window's steps:
the weights counted are those of the experts visited, and a row that skipped the experts is charged none of their
operations. A dispatch's device time is that of the `jit_decode*` programs that start inside its `sched.step` span.
Over a window of steps a context grows by one a step. A program whose entries lack `experts_visited` gives
nothing."""

import re

from benchmark import program_trace, roofline


def read(run, **args):
    rows = getattr(run, "trace_rows", None)
    if not rows:
        return None
    programs = sorted((start, dur) for name, start, dur in program_trace.modules(rows) if name.startswith("decode"))
    least = device = 0.0
    for r in program_trace._named(program_trace.dyn_rows(run), ("sched.step",)):
        stats = r[5]
        if stats.get("kind") not in ("decode", "decode_sample", "decode_multi") or "experts_visited" not in stats:
            continue
        mine = sum(dur for start, dur in programs if r[3] <= start < r[3] + r[4])
        if not mine:
            continue
        steps = int(re.findall(r"\d+", str(stats["key"]))[0]) if stats["kind"] == "decode_multi" else 1
        n = float(stats["rows"])
        ctx_mid = float(stats["ctx"]) + n * (steps - 1) / 2.0
        cost = run.family.decode_step_cost(run.cfg, run.weight_dtype, n, ctx_mid,
                                           experts_visited=float(stats["experts_visited"]) / steps,
                                           skipped_rows=float(stats.get("skipped_rows", 0)) / steps)
        least += steps * roofline.min_seconds(cost, run.device["kind"])["seconds"]
        device += mine / 1e9
    return None if not device else 100.0 * least / device
