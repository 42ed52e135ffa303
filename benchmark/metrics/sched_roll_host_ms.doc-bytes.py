"""`sched_roll_host_ms.doc-bytes`: the host part of a roll. Mean over the window's `sched.roll` spans of the span
less the `sched.launch` and `sched.sync` spans of the same iteration that lie inside it (step log, all of the window).
A program without the span gives nothing."""

from benchmark import program_trace


def read(run, **args):
    log = program_trace.step_log(run)
    if log is None:
        return None
    t0, t1 = program_trace._window_ns(run)
    spans = list(log.spans)
    rolls = [(a, b, step) for name, a, b, step, _ in spans if name == "sched.roll" and a >= t0 and b <= t1]
    waits = [(a, b, step) for name, a, b, step, _ in spans if name in ("sched.launch", program_trace.SYNC)]
    host = [(b - a) - sum(y - x for x, y, s in waits if s == step and x >= a and y <= b) for a, b, step in rolls]
    return None if not host else sum(host) / len(host) / 1e6
