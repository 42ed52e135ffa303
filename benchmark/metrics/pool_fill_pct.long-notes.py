"""`pool_fill_pct.long-notes`: mean over the window's dispatching iterations of the blocks live sequences hold (`pool_blocks` of the
step entries) over the pool's blocks (`scheduler.num_blocks` less the scratch block). A program whose entries lack it
gives nothing."""

from benchmark import cell_readers


def read(run, **args):
    steps = cell_readers.step_entries(run, (*cell_readers.DECODE_KINDS, "mixed", "prefill"))
    held = [a["pool_blocks"] for a in steps or [] if "pool_blocks" in a]
    return None if not held else 100.0 * sum(held) / len(held) / (run.cfg["scheduler"]["num_blocks"] - 1)
