"""`staged_wait_p50_ms.cca.reason`: see `staged_wait_p50_ms.cca.reason.json`; the reduction is `benchmark/program_trace.py::staged_wait_ms`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.staged_wait_ms(run, **args)
