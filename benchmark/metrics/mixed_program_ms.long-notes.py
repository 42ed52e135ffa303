"""`mixed_program_ms.long-notes`: see `mixed_program_ms.long-notes.json`; the reduction is `benchmark/program_trace.py::run_program_ms`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.run_program_ms(run, **args)
