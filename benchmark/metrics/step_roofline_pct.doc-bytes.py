"""`step_roofline_pct.doc-bytes`: least time the chip could take for the decode dispatches of the traced slice over
the device time of their programs.

The accepted reader feeds the family the contexts' lengths; for this family a decode step attends cache rows
(summaries of rolled windows and the current window's keys), which the program writes on its step entries
(`attended` of `dyn:sched.step`, beside `rows` and `ctx`). A dispatch's device time is that of the `jit_decode*`
programs that start inside its `sched.step` span (same clock: both are rows of the one trace). Over a window of
steps a row grows by one a step and stops at its window boundary: the entry's `decode` is the tokens the window
gave (each row's steps until its boundary), `attended_sum` the cache rows those steps attended and `live_steps` the
steps until the last row stopped, so a step that a row did not take is not counted as needed, though the device
ran it. A program whose entries lack `attended` gives
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
        if stats.get("kind") not in ("decode", "decode_sample", "decode_multi") or "attended" not in stats:
            continue
        mine = sum(dur for start, dur in programs if r[3] <= start < r[3] + r[4])
        if not mine:
            continue
        steps = int(re.findall(r"\d+", str(stats["key"]))[0]) if stats["kind"] == "decode_multi" else 1
        n, attended = float(stats["rows"]), float(stats["attended"])
        if "attended_sum" in stats:  # a window: the mean step, over the steps its rows really took
            steps = int(stats["live_steps"])
            n, attended = float(stats["decode"]) / steps, float(stats["attended_sum"]) / steps
        cost = run.family.decode_step_cost(run.cfg, run.weight_dtype, n, attended)
        least += steps * roofline.min_seconds(cost, run.device["kind"])["seconds"]
        device += mine / 1e9
    return None if not device else 100.0 * least / device
