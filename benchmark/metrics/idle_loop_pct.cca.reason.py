"""`idle_loop_pct.cca.reason`: see `idle_loop_pct.cca.reason.json`; the reduction is `benchmark/program_trace.py::idle_pct`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.idle_pct(run, **args)
