"""`rolls_in_window.doc-bytes`: `sched.roll` spans of the program's step log inside the measured window."""

from benchmark import program_trace


def read(run, **args):
    log = program_trace.step_log(run)
    if log is None:
        return None
    t0, t1 = program_trace._window_ns(run)
    return sum(1 for name, a, b, _, _ in list(log.spans) if name == "sched.roll" and a >= t0 and b <= t1)
