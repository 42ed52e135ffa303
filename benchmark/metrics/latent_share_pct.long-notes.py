"""`latent_share_pct.long-notes`: self time of the device operations of the traced slice that are the latent attention's own, in both
kinds of layer, over the slice's busy time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): an array whose last axis is a
cached row (576 lanes in a full layer, 640 as the pool pads it, 1,088 in a sliding one), the absorbed queries and weighted latents of a kind's
heads (`,128,576]`, `,128,512]`, `,64,1088]`, `,64,1024]`), a kind's scores (its heads by the rows of a table width, by
the 2,048 chosen rows, by a ring's 513 or a ring and a chunk), or the low-rank projections' results (24,576 and
16,384 lanes). The norms, the gate and `W_o` (a product into the hidden size) are left out. A trace in which no
operation carries any of them gives nothing."""

from benchmark import cell_readers


def names(cfg: dict) -> tuple:
    H, Hs = cfg["num_attention_heads"], cfg["swa_num_attention_heads"]
    row, ring_row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]
    bs, W, chunk = cfg["engine"]["block_size"], cfg["sliding_window_size"], cfg["scheduler"]["max_prefill_chunk"]
    widths = sorted({w * bs for w in (4, 6, 8, 12, 16, 24, 32, 48, 64) if w * bs < cfg["engine"]["max_seq_len"]}
                    | {-(-cfg["engine"]["max_seq_len"] // bs) * bs, cfg["index_topk"]})
    lanes = (row, -(-row // 128) * 128, ring_row, H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]),
             Hs * (cfg["swa_qk_nope_head_dim"] + cfg["swa_qk_rope_head_dim"]))
    return (*(f",{n}]" for n in lanes), f",{H},{cfg['kv_lora_rank']}]", f",{Hs},{cfg['swa_kv_lora_rank']}]",
            *(f",{H},{s}]" for s in widths), f",{Hs},{W}]", f",{Hs},{W + chunk}]")


def read(run, **args):
    return cell_readers.op_share_pct(run, names(run.cfg))
