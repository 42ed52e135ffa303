"""`sched_slots_host_ms.reason`: mean duration of the window's `sched.slots` spans (step log, all of the window): the
host's part of a sequence taking its state slot. A program without the span gives nothing."""

from benchmark import program_trace


def read(run, **args):
    log = program_trace.step_log(run)
    if log is None:
        return None
    t0, t1 = program_trace._window_ns(run)
    spans = [b - a for name, a, b, _, _ in list(log.spans) if name == "sched.slots" and a >= t0 and b <= t1]
    return None if not spans else sum(spans) / len(spans) / 1e6
