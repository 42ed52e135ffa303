"""`ssm_update_rows_roofline_pct.chat-many`: least time the chip could take for the launches of the Pallas kernel
`ssm_update_rows` in the traced slice (the family's `ssm_update_cost`: each live row's float32 state read and written
once, its inputs in and its output out) over the device time of those launches.

A launch advances the decode rows of its dispatch in one state-space layer: a `dyn:sched.step` entry of `rows` rows
needs one launch a state-space layer and step (a window of 8 steps: 8 a layer), and its device time is that of the
launches that start inside its span. The rows that pad a batch bucket and the idle launch of a chunk that has no
decode row beside it (`hybrid._mamba_mixer`) move the scratch slot and need nothing: their time counts, their bytes
do not. A family without `ssm_update_cost`, or a trace
without the kernel or without `ssm_rows` on its step entries, gives nothing."""

import re

from benchmark import program_trace, roofline, trace as tr

KERNEL = "ssm_update_rows"


def read(run, **args):
    cost = getattr(run.family, "ssm_update_cost", None)
    rows = getattr(run, "trace_rows", None)
    if cost is None or not rows:
        return None
    launches = sorted((r[3], r[4]) for r in rows if r[1] == tr.OPS_LINE and tr.stable_name(r[2]) == KERNEL)
    if not launches:
        return None
    layers = run.cfg["layer_types"][: run.cfg["num_hidden_layers"]].count("mamba")
    least = device = 0.0
    for r in program_trace._named(program_trace.dyn_rows(run), ("sched.step",)):
        stats = r[5]
        if "ssm_rows" not in stats:
            continue
        kind = stats.get("kind")
        steps = int(re.findall(r"\d+", str(stats["key"]))[0]) if kind == "decode_multi" else 0 if kind == "prefill" else 1
        mine = [dur for start, dur in launches if r[3] <= start < r[3] + r[4]]
        needed = min(steps * layers, len(mine))  # a span the slice cuts holds fewer launches than its dispatch made
        least += needed * roofline.min_seconds(cost(run.cfg, float(stats["rows"])), run.device["kind"])["seconds"]
        device += sum(mine) / 1e9
    return None if not device else 100.0 * least / device
