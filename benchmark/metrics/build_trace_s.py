"""`build_trace_s`: see `build_trace_s.json`; the reduction is in `benchmark/build_log.py`."""

from benchmark import build_log


def read(run, **args):
    return build_log.seconds(run, 'trace_s')
