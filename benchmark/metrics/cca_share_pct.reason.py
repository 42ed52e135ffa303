"""`cca_share_pct.reason`: self time of the device operations of the traced slice that are the attention sublayer's
own, over the slice's busy time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): the attention kernel
(`ragged_paged_attention`), and any operation on an array whose last axis is the convolutions' channels (queries and
keys, 1280), the fused projection (1536), the slot's columns (2688), the latent query (1024), the ten heads the second
convolution is grouped by, the 256-lane key and value rows with their two heads spelt out (`,2,128]`), or the pool's
pages (`,128,256]`: the step's one scatter of its rows). A product
into the hidden size whose line names only its 2048-lane result (`W_o` fused alone) is left out, as are the norms and
the residual merge, which work on `hidden_size` lanes like the expert sublayer's.
A trace in which no operation carries any of them gives nothing."""

from benchmark import cell_readers


def names(cfg: dict) -> tuple:
    d, Hq, Hk = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q, kv = Hq * d, Hk * d
    C = q + kv
    lanes = (C, C + kv, 2 * C + kv // 2, q)
    return ("ragged_paged_attention", *(f",{n}]" for n in lanes), *(f"[{n}]" for n in lanes),
            f",{Hq + Hk},{d}]", f",{Hq + Hk},{2 * d}]", f",{Hk},{d}]", f",{Hq},{d}]", f",{cfg['engine']['block_size']},{kv}]")


def read(run, **args):
    return cell_readers.op_share_pct(run, names(run.cfg))
