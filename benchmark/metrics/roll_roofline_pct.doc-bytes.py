"""`roll_roofline_pct.doc-bytes`: the bytes one roll needs (the family's `roll_cost`: a window's rows read and its
summaries written, in every layer) at the chip's peak bandwidth, over the mean device time of `jit_eva_roll` in the
traced slice. A family without `roll_cost`, or a slice without a roll, gives nothing."""

from benchmark import program_trace, roofline


def read(run, **args):
    cost = getattr(run.family, "roll_cost", None)
    ms = program_trace.run_program_ms(run, prefix="eva_roll", per_step=False)
    if cost is None or not ms:
        return None
    least_s = roofline.min_seconds(cost(run.cfg), run.device["kind"])["seconds"]
    return 100.0 * least_s / (ms / 1e3)
