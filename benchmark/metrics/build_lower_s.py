"""`build_lower_s`: see `build_lower_s.json`; the reduction is in `benchmark/build_log.py`."""

from benchmark import build_log


def read(run, **args):
    return build_log.seconds(run, 'lower_s')
