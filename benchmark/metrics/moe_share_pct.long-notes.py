"""`moe_share_pct.long-notes`: self time of the grouped GEMMs over the held experts in the traced slice, over the slice's busy time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): XLA's `ragged-dot`, or the megablox
kernel `gmm`. The router, the sort and the combine are left out. A trace in which no operation carries any of them
gives nothing."""

from benchmark import cell_readers


def read(run, **args):
    return cell_readers.op_share_pct(run, ("ragged-dot", "ragged_dot", "gmm"))
