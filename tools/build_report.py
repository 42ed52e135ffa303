#!/usr/bin/env python3
"""What JAX built in one run of a benchmark cell, from the program's build log
(``dynamo_tpu/engine/compile_cache.py``; PERF.md §6, PR 39).

    chiprun -- python3 tools/build_report.py --workload <cell> --seed <n> [--seconds <s>] [--trace <0|1>] [--deepest-stack]
    JAX_PLATFORMS=cpu python3 tools/build_report.py --workload <cell> --seed 1 --seconds 4 --rehearse

Runs ``benchmark/run.py`` in this process with the arguments it is given and
prints one more JSON line before the result line, ``{"phase": "build_log", ...}``:
what ``/debug/state`` shows under ``build`` for the run's engine (the
``engine.build`` span as trace + lowering + backend + other seconds, the same
by kind, the costliest keys, the eager executables by name, what was built
since warm-up, ``in_one_chunk`` beside ``executables``: how many of them
were built below ``compile_cache.in_one_chunk``'s frame, which is all of a
set-up's and none of serving's, and ``store_hits`` / ``store_misses`` beside
``cache_hits`` / ``cache_misses``: how many were built from a module the
program store held, ``engine/program_store.py``, and how many were traced,
lowered and written to it; ``program_store``: its files and bytes on disk,
this checkout's generation and all), and beside it the whole process's count and backend seconds
(what the harness's ``CompileMeter`` prints as ``executables`` and
``compile_seconds``) and what the harness built before ``engine.build`` (its
weights, its output check). The last line is still the run's result.

``--deepest-stack`` (a run of its own: the walk makes frame objects and costs
seconds) also gives ``deepest_stack``: the most bytes of Python frames that
stood under a call of JAX's lowering (``mlir.jaxpr_subcomp``, which calls
every lowering rule, ``mlir._emit_lowering_rule_as_fun`` and Mosaic's
``jaxpr_subcomp``), as frames and bytes (8 × ``co_framesize``), in all and
below ``in_one_chunk``'s frame: what ``compile_cache.ANCHOR_SLOTS`` is sized from.
"""

from __future__ import annotations

import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


FRAME_SPECIALS = 9  # slots of CPython 3.12's _PyInterpreterFrame before its locals (Include/internal/pycore_frame.h)
DEEPEST: dict = {}  # frames, bytes, under (the watched call); below_anchor_frames, below_anchor_bytes (the deepest below it)


def frame_bytes(code) -> int:
    """8 × ``co_framesize``: locals, cells and free variables, the value stack, the frame's own fields."""
    cells = sum(name not in code.co_varnames for name in code.co_cellvars)
    return 8 * (len(code.co_varnames) + cells + len(code.co_freevars) + code.co_stacksize + FRAME_SPECIALS)


def watch_stack() -> None:
    """Walk the stack at every call of JAX's lowering and keep the deepest."""
    from jax._src.interpreters import mlir
    from jax._src.pallas.mosaic import lowering as mosaic

    from dynamo_tpu.engine import compile_cache

    anchor = compile_cache._anchor and compile_cache._anchor.__code__

    def watched(module, name):
        fn = getattr(module, name)

        def walk(*args, **kwargs):
            frames = size = 0
            f = sys._getframe(1)
            while f is not None:
                if f.f_code is anchor:  # what stands below it is the large chunk's; its own megabyte is not counted
                    if size > DEEPEST.get("below_anchor_bytes", 0):
                        DEEPEST.update(below_anchor_frames=frames, below_anchor_bytes=size)
                else:
                    frames, size = frames + 1, size + frame_bytes(f.f_code)
                f = f.f_back
            if size > DEEPEST.get("bytes", 0):
                DEEPEST.update(frames=frames, bytes=size, under=f"{module.__name__.rsplit('.', 1)[-1]}.{name}")
            return fn(*args, **kwargs)

        setattr(module, name, walk)

    watched(mlir, "jaxpr_subcomp")
    watched(mlir, "_emit_lowering_rule_as_fun")
    watched(mosaic, "jaxpr_subcomp")


def store_on_disk() -> dict:
    """The program store's files and bytes: every generation's, and this source's."""
    from dynamo_tpu.engine.compile_cache import program_store_dir
    from dynamo_tpu.engine.program_store import ProgramStore

    root = program_store_dir()
    if root is None or not os.path.isdir(root):
        return {}
    sizes = {os.path.join(d, f): os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(root) for f in fs}
    mine = ProgramStore(root, "").dir
    return {"program_store": {"dir": root, "files": len(sizes), "bytes": sum(sizes.values()),
                              "generation_files": sum(p.startswith(mine) for p in sizes),
                              "generation_bytes": sum(n for p, n in sizes.items() if p.startswith(mine))}}


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
            **({"deepest_stack": DEEPEST} if DEEPEST else {}), **store_on_disk(),
            **BUILD_LOG.summary(since)}


def finish_after_report(result, compared):
    run.emit(report())
    finish(result, compared)


if __name__ == "__main__":
    from benchmark import run

    finish, run.finish = run.finish, finish_after_report
    if "--deepest-stack" in sys.argv:
        sys.argv.remove("--deepest-stack")
        watch_stack()
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
