"""`ssm_share_pct.chat-many`: self time of the device operations of the traced slice that are the state-space mixer's own, over the slice's busy
time.

An operation is told by what stands in its HLO line (`benchmark/cell_readers.py`): the Pallas kernel `ssm_update_rows`
(the decode rows' state update in place), any operation on an array shaped as the recurrent state is written
(`[..., heads, d_head, d_state]`), stored (`[..., heads/g, d_state, g*d_head]`) or read by the chunked scan (the XLA
form's gather, update and scatter, the scan's products with the state, `B` and `C` broadcast along its lanes) and any
on `conv_dim` lanes (the convolution and the split of its lanes). The mixer's two projections are plain matmuls
over the hidden size and are left out.
A trace in which no operation carries any of them gives nothing."""

from benchmark import cell_readers


def names(cfg: dict) -> tuple:
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    cd = H * P + 2 * cfg["mamba_n_groups"] * N
    g = 128 // P if 128 % P == 0 and H % (128 // P) == 0 else 1  # heads side by side on the lanes (ModelConfig.mamba_state_shape)
    state = (f"{H},{P},{N}]", f"{H // g},{N},{g * P}]", f"{H // g},{N},{g},{P}]")  # as the recurrence writes it, as stored, as the chunk reads it
    return ("ssm_update_rows", *state, f",{cd}]", f"[{cd}]")


def read(run, **args):
    return cell_readers.op_share_pct(run, names(run.cfg))
