"""`experts_visited_pct.reason`: over the window's decode dispatches, the (layer, expert) pairs the rows fell on
(`experts_visited`, summed over a window's steps) over steps x layers x experts: the share of the experts' weights a
decode step has to read. A program whose entries lack the count gives nothing."""

import re

from benchmark import cell_readers


def read(run, **args):
    steps = cell_readers.step_entries(run, cell_readers.DECODE_KINDS)
    if not steps:
        return None
    per_step = run.cfg["num_hidden_layers"] * run.cfg["num_experts"]
    visited = possible = 0
    for a in steps:
        if "experts_visited" not in a:
            continue
        n = int(re.findall(r"\d+", str(a["key"]))[0]) if a["kind"] == "decode_multi" else 1
        visited, possible = visited + a["experts_visited"], possible + n * per_step
    return None if not possible else 100.0 * visited / possible
