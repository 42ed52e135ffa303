#!/usr/bin/env python3
"""What JAX built in one run of a benchmark cell, from the program's build log
(``dynamo_tpu/engine/compile_cache.py``; PERF.md §6, PR 39).

    chiprun -- python3 tools/build_report.py --workload <cell> --seed <n> [--seconds <s>] [--trace <0|1>]
    JAX_PLATFORMS=cpu python3 tools/build_report.py --workload <cell> --seed 1 --seconds 4 --rehearse

Runs ``benchmark/run.py`` in this process with the arguments it is given and
prints one more JSON line before the result line, ``{"phase": "build_log", ...}``:
what ``/debug/state`` shows under ``build`` for the run's engine (the
``engine.build`` span as trace + lowering + backend + other seconds, the same
by kind, the costliest keys, the eager executables by name, what was built
since warm-up), and beside it the whole process's count and backend seconds
(what the harness's ``CompileMeter`` prints as ``executables`` and
``compile_seconds``) and what the harness built before ``engine.build`` (its
weights, its output check). The last line is still the run's result.
"""

from __future__ import annotations

import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def report() -> dict:
    from dynamo_tpu.engine.compile_cache import BUILD, BUILD_LOG

    entries = list(BUILD_LOG.entries)
    builds = [s for s in BUILD_LOG.scopes if s[0] == BUILD]
    since = builds[-1][2] if builds else 0
    before = [e for e in entries if e.t_ns < since]
    return {"phase": "build_log", "executables_whole_run": BUILD_LOG.total,
            "backend_s_whole_run": sum(e.backend_s for e in entries),
            "before_engine_build": {"executables": len(before), "trace_s": sum(e.trace_s for e in before),
                                    "lower_s": sum(e.lower_s for e in before), "backend_s": sum(e.backend_s for e in before)},
            **BUILD_LOG.summary(since)}


def finish_after_report(result, compared):
    run.emit(report())
    finish(result, compared)


if __name__ == "__main__":
    from benchmark import run

    finish, run.finish = run.finish, finish_after_report
    # (``run.main`` is called from the module itself, as ``benchmark/run.py`` calls its own, and this module's frame is
    # the size of that one: more bytes of Python frames under the jitted calls would move what their lowering costs,
    # and the report would not be of the run the driver makes: PERF.md section 6, PR 39.)
    try:
        code = run.main()
    except SystemExit:
        raise
    except BaseException:  # noqa: BLE001 - any failure: no result line, non-zero exit
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # as benchmark/run.py leaves: no thread of the program may keep the chip
