"""`idle_pre_launch_pct.ssm.chat-many`: see `idle_pre_launch_pct.ssm.chat-many.json`; the reduction is `benchmark/program_trace.py::idle_pct`."""

from benchmark import program_trace


def read(run, **args):
    return program_trace.idle_pct(run, **args)
