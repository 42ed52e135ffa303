"""`indexed_rows_per_ctx_row.long-notes`: over the window's decode dispatches, the cached rows the full layers' queries chose (`indexed_rows`) over
the rows their indexers scored (`index_ctx`): 2,048 over the context where the mechanism is at work, 1 where every row
is attended. A program whose entries lack the counts gives nothing."""

from benchmark import cell_readers


def read(run, **args):
    steps = [a for a in cell_readers.step_entries(run, cell_readers.DECODE_KINDS) or [] if a.get("index_ctx")]
    return None if not steps else sum(a["indexed_rows"] for a in steps) / sum(a["index_ctx"] for a in steps)
