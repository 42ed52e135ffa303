"""Reductions over the program's build log (``dynamo_tpu/engine/compile_cache.py``,
PR 39): one entry an executable JAX built, with its key and its trace,
lowering and backend seconds, and the intervals of ``engine.build`` and its
phases, all on ``time.monotonic_ns()``.

The four ``build_*`` metrics cover set-up from the start of ``engine.build``
to the opening of the window (``run.window[0]``): the engine's own build and
what the warm-up stream and the ramp still met. What the harness builds
before that (its weights, its output check) is the harness's and is left out.

A program without the log (any commit before PR 39) gives ``None``
everywhere: the runner then leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Optional, Tuple

BUILD = "engine.build"
EAGER = "eager"


def set_up(run) -> Optional[Tuple[list, Tuple[int, int]]]:
    """(the entries from the start of ``engine.build`` to the window's opening,
    that scope's interval), or None where the program keeps no such log."""
    engine = getattr(getattr(run, "hooks", None), "engine", None)
    flight = getattr(getattr(engine, "scheduler", None), "flight", None)
    log, since = getattr(flight, "builds", None), getattr(flight, "since_ns", None)
    if log is None or since is None:
        return None
    build = next(((t0, t1) for name, _, t0, t1 in list(log.scopes) if name == BUILD and t0 == since), None)
    if build is None:
        return None
    opens = int(run.window[0] * 1e9)
    return [e for e in list(log.entries) if build[0] <= e.t_ns <= opens], build


def seconds(run, part: str) -> Optional[float]:
    """The entries' ``trace_s`` (the outer trace alone) or ``lower_s``, summed."""
    found = set_up(run)
    return None if found is None else sum(getattr(e, part) for e in found[0])


def other_s(run) -> Optional[float]:
    """The ``engine.build`` scope less the trace, lowering and backend seconds
    of the entries inside it: the pool, placement, the warm-up dispatches' own
    run time, Python that is neither tracing nor lowering."""
    found = set_up(run)
    if found is None:
        return None
    entries, (t0, t1) = found
    return (t1 - t0) / 1e9 - sum(e.trace_s + e.lower_s + e.backend_s for e in entries if e.t_ns <= t1)


def eager_executables(run) -> Optional[int]:
    """Entries in no scope: ``jnp.zeros``, fills, converts, each a program of its own."""
    found = set_up(run)
    return None if found is None else sum(e.kind == EAGER for e in found[0])
