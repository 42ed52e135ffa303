"""`indexer_share_pct.long-notes`: self time of the device operations of the traced slice that are the learned indexer's own, over the
slice's busy time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): the index scores (the 64 index
heads by the rows of a table width, `,64,<rows>]`), the exact top-2,048 (XLA's `sort` and `topk` / `top-k`
operations; `lax.top_k` is what runs, no kernel) and the index keys' pages (`,<block>,128]`). The gather of the chosen
rows carries a cached row's 576 lanes and is counted under `latent_share_pct.long-notes`. A trace in which no
operation carries any of them gives nothing."""

from benchmark import cell_readers


def names(cfg: dict) -> tuple:
    Hi, bs = cfg["index_n_heads"], cfg["engine"]["block_size"]
    widths = sorted({w * bs for w in (4, 6, 8, 12, 16, 24, 32, 48, 64) if w * bs < cfg["engine"]["max_seq_len"]}
                    | {-(-cfg["engine"]["max_seq_len"] // bs) * bs})
    return ("sort", "topk", "top-k", "TopK", f",{bs},{cfg['index_head_dim']}]", *(f",{Hi},{s}]" for s in widths))


def read(run, **args):
    return cell_readers.op_share_pct(run, names(run.cfg))
