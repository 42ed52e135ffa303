"""`roll_program_ms.doc-bytes`: mean device duration of the `jit_eva_roll` programs of the traced slice; the
reduction is `benchmark/program_trace.py::run_program_ms`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.run_program_ms(run, prefix="eva_roll", per_step=False)
