"""`frontend_busy_pct.cca.reason`: see `frontend_busy_pct.cca.reason.json`; the reduction is `benchmark/program_trace.py::frontend_busy_pct`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.frontend_busy_pct(run, **args)
