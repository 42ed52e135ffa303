"""`build_eager_executables`: see `build_eager_executables.json`; the reduction is in `benchmark/build_log.py`."""

from benchmark import build_log


def read(run, **args):
    return build_log.eager_executables(run)
