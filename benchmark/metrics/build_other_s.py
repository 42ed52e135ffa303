"""`build_other_s`: see `build_other_s.json`; the reduction is in `benchmark/build_log.py`."""

from benchmark import build_log


def read(run, **args):
    return build_log.other_s(run)
