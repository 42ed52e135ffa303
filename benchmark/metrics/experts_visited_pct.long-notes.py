"""`experts_visited_pct.long-notes`: over the window's decode dispatches, the (layer, held expert) pairs the rows fell on (`experts_visited`,
summed over a window's steps) over steps x expert layers x held experts: the share of the held experts' weights a
decode step has to read. A program whose entries lack the count gives nothing."""

import re

from benchmark import cell_readers


def _steps(a) -> int:
    return int(re.findall(r"\d+", str(a["key"]))[0]) if a["kind"] == "decode_multi" else 1


def read(run, **args):
    steps = [a for a in cell_readers.step_entries(run, cell_readers.DECODE_KINDS) or [] if "experts_visited" in a]
    per_step = (run.cfg["num_hidden_layers"] - run.cfg["first_k_dense_replace"]) * run.cfg["n_routed_experts"]
    possible = sum(_steps(a) for a in steps) * per_step
    return None if not possible else 100.0 * sum(a["experts_visited"] for a in steps) / possible
