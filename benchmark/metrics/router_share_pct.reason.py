"""`router_share_pct.reason`: self time of the device operations of the traced slice that are the router's, over the
slice's busy time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): any operation on an array whose
last axis is `router_hidden_size` (the down-projection's result, the carried state, the MLP's two hidden layers) or
the router's choices (`num_experts` + 1: the softmax, the bias, the argmax). The key and value rows are as wide as
the router (256 lanes) where they are written merged: those operations carry the pool's block size or the two heads
in the same line and are told apart by it (`,128,256]`: such lines, and the attention kernel's, are taken out before
the count; they are `cca_share_pct.reason`'s), but a plain `[rows,256]` copy of them counts here.
The argsort of the assignments and the scatter-add that combines the experts' rows work on `hidden_size` lanes or on
bare row counts and are left out. A trace in which no operation carries any of them gives nothing."""

import types

from benchmark import cell_readers, trace as tr


def names(cfg: dict) -> tuple:
    r, n = cfg["router_hidden_size"], cfg["num_experts"] + 1
    return (f",{r}]", f",{n}]")


def read(run, **args):
    rows = getattr(run, "trace_rows", None)
    if not rows:
        return None
    block = f",{run.cfg['engine']['block_size']},{run.cfg['num_key_value_heads'] * run.cfg['head_dim']}]"
    kept = [r for r in rows if not (r[1] == tr.OPS_LINE and (block in r[2] or "ragged_paged_attention" in r[2]))]
    return cell_readers.op_share_pct(types.SimpleNamespace(trace_rows=kept, trace_busy=getattr(run, "trace_busy", None)),
                                     names(run.cfg))
