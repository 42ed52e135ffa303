"""`expert_rows_mean.reason`: over the window's decode dispatches, the rows that fell on an expert
(`held_assignments` of the step entries: with one expert a token, the rows that did not draw the skip choice) over the
(layer, expert) pairs they fell on (`experts_visited`): the rows of the mean GEMM group. A program whose entries lack
the counts gives nothing."""

from benchmark import cell_readers


def read(run, **args):
    steps = cell_readers.step_entries(run, cell_readers.DECODE_KINDS)
    if not steps:
        return None
    held = sum(a.get("held_assignments", 0) for a in steps)
    visited = sum(a.get("experts_visited", 0) for a in steps)
    return None if not visited else held / visited
