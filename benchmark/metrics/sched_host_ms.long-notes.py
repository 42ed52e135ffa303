"""`sched_host_ms.long-notes`: see `sched_host_ms.long-notes.json`; the reduction is `benchmark/program_trace.py::sched_host_ms`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.sched_host_ms(run, **args)
