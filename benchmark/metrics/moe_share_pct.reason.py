"""`moe_share_pct.reason`: self time of the grouped GEMMs over the experts in the traced slice, over the slice's busy
time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): XLA's `ragged-dot`, or the megablox
kernel `gmm`. An expert here is as wide as the hidden size (`moe_intermediate_size` = `hidden_size` = 2048), so the
activation between the products cannot be told from the rest of the stack by its lanes and is left out, as are the
router (`router_share_pct.reason`), the sort and the combine.
A trace in which no operation carries any of them gives nothing."""

from benchmark import cell_readers


def read(run, **args):
    return cell_readers.op_share_pct(run, ("ragged-dot", "ragged_dot", "gmm"))
