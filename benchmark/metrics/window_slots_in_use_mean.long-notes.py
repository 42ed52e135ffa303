"""`window_slots_in_use_mean.long-notes`: mean over the window's dispatching iterations of the ring slots held (`window_slots` of the step
entries). A program whose entries lack it gives nothing."""

from benchmark import cell_readers


def read(run, **args):
    steps = cell_readers.step_entries(run, (*cell_readers.DECODE_KINDS, "mixed", "prefill"))
    held = [a["window_slots"] for a in steps or [] if "window_slots" in a]
    return None if not held else sum(held) / len(held)
