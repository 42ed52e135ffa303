"""`attended_rows_per_ctx_byte.doc-bytes`: over the window's dispatches that carried decode rows, the cache rows
their contexts hold (`attended` on the program's step entries: summaries of rolled windows and the current window's
keys) over the contexts' bytes (`ctx`). A program whose entries lack `attended` gives nothing."""

from benchmark import program_trace

DECODE_KINDS = ("decode", "decode_sample", "decode_multi", "mixed")


def read(run, **args):
    log = program_trace.step_log(run)
    if log is None:
        return None
    t0, t1 = program_trace._window_ns(run)
    rows = ctx = 0
    for name, a, b, _, attrs in list(log.spans):
        if name == "sched.step" and a >= t0 and b <= t1 and attrs and attrs.get("kind") in DECODE_KINDS \
                and "attended" in attrs:
            rows, ctx = rows + attrs["attended"], ctx + attrs["ctx"]
    return None if not ctx else rows / ctx
