"""`moe_share_pct.chat-many`: self time of the device operations of the traced slice that are the expert layer's, over the slice's busy
time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): the grouped
GEMMs over the held experts (XLA's `ragged-dot`, or the megablox kernel `gmm`), any operation on `intermediate_size`
lanes (the experts' activation between the products) and any on `shared_intermediate_size` lanes (the shared
expert). Routing, the sort and the combine work on `hidden_size` lanes and are left out.
A trace in which no operation carries any of them gives nothing."""

from benchmark import cell_readers


def names(cfg: dict) -> tuple:
    return ("ragged-dot", "ragged_dot", "gmm", f",{cfg['intermediate_size']}]", f",{cfg['shared_intermediate_size']}]")


def read(run, **args):
    return cell_readers.op_share_pct(run, names(run.cfg))
