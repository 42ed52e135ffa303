"""`programs_per_dispatch.cca.reason`: see `programs_per_dispatch.cca.reason.json`; the reduction is `benchmark/program_trace.py::programs_per_dispatch`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.programs_per_dispatch(run, **args)
