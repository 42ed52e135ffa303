"""`skipped_rows_pct.reason`: over the window's decode dispatches, the (row, layer) pairs that drew the router's skip
choice (`skipped_rows` of the step entries, summed over layers and a window's steps) over rows x steps x layers. A
program whose entries lack the count gives nothing."""

import re

from benchmark import cell_readers


def read(run, **args):
    steps = cell_readers.step_entries(run, cell_readers.DECODE_KINDS)
    if not steps:
        return None
    layers = run.cfg["num_hidden_layers"]
    skipped = possible = 0
    for a in steps:
        if "skipped_rows" not in a:
            continue
        n = int(re.findall(r"\d+", str(a["key"]))[0]) if a["kind"] == "decode_multi" else 1
        skipped, possible = skipped + a["skipped_rows"], possible + n * layers * a["rows"]
    return None if not possible else 100.0 * skipped / possible
