"""`attention_share_pct.doc-bytes`: self time of the attention kernels (the custom calls named below: the ragged
megakernel, the paged kernel of the decode rows, the flash kernel of a chunk's own keys) on the first device plane of
the traced slice, over the slice's busy time. Left out, because no name marks it: the chunk's prefix piece, which
runs as XLA fusions (block fetches and a score product) beside the flash kernel."""

from benchmark import trace as tr

KERNELS = ("ragged_paged_attention", "paged_decode_partials", "flash_chunk_attention")


def read(run, **args):
    rows, busy = getattr(run, "trace_rows", None), getattr(run, "trace_busy", None)
    planes = tr.device_planes(rows) if rows else []
    if not planes or not busy or not busy.get("busy_s"):
        return None
    t0, t1 = tr.window_of(rows)
    mine = sum(ns for name, ns in tr.self_times(rows, planes[0], t0, t1) if name.startswith(KERNELS))
    return 100.0 * mine / 1e9 / busy["busy_s"]
