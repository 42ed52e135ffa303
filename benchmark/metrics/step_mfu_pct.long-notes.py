"""`step_mfu_pct.long-notes`: the share of the whole step's roofline: least time the chip could take for the decode
dispatches of the traced slice over the device time of their programs.

What a step needs is the family's `decode_step_cost`, fed from the program's step entries (`dyn:sched.step` rows of the
one trace): `rows`, `ctx`, `experts_visited` (the (layer, held expert) pairs the dispatch's rows fell on),
`held_assignments` (the assignments that fell on held experts), `indexed_rows` (the cached rows the full layers'
queries chose) and `index_ctx` (the rows their indexers scored), all summed over layers and over the window's steps.
A dispatch's device time is that of the `jit_decode*` programs that start inside its `sched.step` span. A program
whose entries lack `indexed_rows` gives nothing."""

import re

from benchmark import program_trace, roofline

COUNTS = ("experts_visited", "held_assignments", "indexed_rows", "index_ctx")


def read(run, **args):
    rows = getattr(run, "trace_rows", None)
    if not rows:
        return None
    programs = sorted((start, dur) for name, start, dur in program_trace.modules(rows) if name.startswith("decode"))
    least = device = 0.0
    for r in program_trace._named(program_trace.dyn_rows(run), ("sched.step",)):
        stats = r[5]
        if stats.get("kind") not in ("decode", "decode_sample", "decode_multi") or any(k not in stats for k in COUNTS):
            continue
        mine = sum(dur for start, dur in programs if r[3] <= start < r[3] + r[4])
        if not mine:
            continue
        steps = int(re.findall(r"\d+", str(stats["key"]))[0]) if stats["kind"] == "decode_multi" else 1
        n = float(stats["rows"])
        cost = run.family.decode_step_cost(run.cfg, run.weight_dtype, n, float(stats["ctx"]) + n * (steps - 1) / 2.0,
                                           **{k: float(stats[k]) / steps for k in COUNTS})
        least += steps * roofline.min_seconds(cost, run.device["kind"])["seconds"]
        device += mine / 1e9
    return None if not device else 100.0 * least / device
