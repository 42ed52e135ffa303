"""JAX's persistent compilation cache, placed from outside or at one fixed
path, and the build log: what JAX built in this process, one entry an
executable, from JAX's own events.

The cache directory is part of the cache key's environment: a directory that
moves never hits. So there is exactly one rule, applied where an engine is
built (``TpuEngine.build``) and by scripts that jit before that:

- ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; set nothing.
- unset → ``<checkout>/.jax_cache``, derived from this package's own
  location (git-ignored). Never a temp name, a pid or a time.

``tests/conftest.py`` keeps the cache off for tests.

The program store (``engine/program_store.py``) lives under the same rule, in
``programs/`` INSIDE that directory (``program_store_dir``): JAX's eviction
counts and deletes ``*-cache`` files at the directory's top level alone, so it
neither counts nor deletes the store, and a machine that keeps the cache from
one run to the next keeps the store with it. Where the persistent cache is off
the store is off. The cache holds executables by the hash of their HLO, which
a process knows only after it has traced and lowered the program in Python;
the store holds the lowered programs themselves (``jax.export``) by a digest
of what the tracing would read, so a warm set-up traces and lowers nothing it
has lowered before. An entry of the build log says whether its program came
from the store (``store``: ``hit``, ``miss`` or none).

The build log. ``jax.monitoring`` reports every trace
(``jaxpr_trace_duration``), every lowering (``jaxpr_to_mlir_module_duration``)
and every compile or load from the persistent cache
(``backend_compile_duration``) with the function's name, and whether the cache
held the executable (``cache_hits`` / ``cache_misses``), on the thread that
called the jitted function. ``enable_compile_cache`` registers ``BUILD_LOG``
for them, once a process. An entry closes on the backend event and takes the
OUTER trace alone (the last trace before the lowering that the lowering's
name repeats); the jitted helpers traced inside it (each operator, index and
``jax.numpy`` call on a traced array at a new shape) are counted in
``nested_traces``, not added: the outer trace's seconds already hold theirs.

An entry knows its key from the innermost ``build.key`` scope open on its
thread (``BuildLog.scope``: ``Scheduler.warmup`` opens one around each
executable key it warms) or, in serving, from the open ``sched.launch``
(``BuildLog.launching``). In no scope it is an eager executable
(``jnp.zeros``, a fill, a convert), kept with its ``fun_name``. The phases of
a set-up (``engine.build`` > ``build.params``, ``build.scheduler``,
``build.warmup``) are scopes too, and the log keeps every scope's interval
beside the entries: the step log's ring turns over inside one minute of
serving, this one holds a process's set-up until someone reads it.

One stack chunk for a set-up (``in_one_chunk``). CPython 3.11+ keeps a thread's
Python frames in chunks of 16 KiB: a call whose frame is the first of a new
chunk maps it and the return that empties it unmaps it, so a loop whose callee
sits on a chunk's edge pays an ``mmap`` and a ``munmap`` every iteration (on the
chip's host 100 µs a call against 0.07: ``tools/stack_chunk_probe.py``). JAX's
lowering is deep recursion with loops at every level, and half to two thirds of
a warm set-up's lowering seconds were those system calls (PERF.md §6, PR 42).
``TpuEngine.build`` therefore runs below one frame too large for a 16 KiB
chunk: CPython maps one large chunk for it, once, and every frame of the
set-up lives in the room that frame leaves free. An entry says whether it was
built below that frame (``in_one_chunk``); one built in serving was not.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit", "/jax/compilation_cache/cache_misses": "miss"}

BUILD = "engine.build"
KEY_SCOPE = "build.key"
EAGER = "eager"  # the kind of an entry in no scope
SERVING = "serving"  # the phase of an entry outside every scope once warm-up is done
# A run builds 170-310 executables and opens about as many scopes; a process
# that keeps building (an unbounded key space) turns over here.
BUILD_LOG_SIZE = 4096
TOP = 10
PARTS = ("trace_s", "lower_s", "backend_s")

# The stack slots of ``in_one_chunk``'s frame: 1 MiB. ``push_chunk`` (CPython's Python/pystate.c) gives a frame that fits no
# chunk one of 8 × (slots + 1000) bytes rounded up to a power of two, here 2 MiB, and the frames below live in the 1,048,456 B it
# leaves free: 24 × the 43,888 B (114 frames) lowering's deepest call stood on below it in any of the five cells (PERF.md §6, PR 42).
ANCHOR_SLOTS = 1 << 17


class _Stack(threading.local):
    anchored = False  # this thread is below ``in_one_chunk``'s frame


_stack = _Stack()


def _make_anchor() -> Optional[Callable]:
    """The frame a set-up runs below, or None where the interpreter keeps no
    frames in chunks (anything but CPython 3.11+). Stack slots are not
    initialised, so the frame costs its mapping and not a write."""
    if sys.implementation.name != "cpython" or sys.version_info < (3, 11):
        return None

    def anchor(fn, args, kwargs):
        return fn(*args, **kwargs)

    anchor.__code__ = anchor.__code__.replace(co_stacksize=ANCHOR_SLOTS)
    return anchor


_anchor = _make_anchor()


def in_one_chunk(fn: Callable, *args: Any, **kwargs: Any) -> Any:
    """``fn(*args, **kwargs)`` with every Python frame below it in one stack
    chunk, mapped at this call and given back at its return (9 µs here, 0.1 ms
    on the chip's host): for a set-up, not for a dispatch."""
    if _anchor is None:
        return fn(*args, **kwargs)
    was, _stack.anchored = _stack.anchored, True
    try:
        return _anchor(fn, args, kwargs)
    finally:
        _stack.anchored = was


class BuildEntry(NamedTuple):
    """One executable JAX built (compiled, or loaded from the persistent cache)."""

    t_ns: int  # time.monotonic_ns() at the backend event: the step log's clock
    thread: int
    fun_name: str  # as the backend event names it: "jit(mixed_step)"
    phase: Optional[str]  # innermost phase scope, else "serving" after warm-up, else None
    kind: str  # of the build.key scope or the open sched.launch, else "eager"
    key: Optional[tuple]
    trace_s: float  # the OUTER trace alone
    lower_s: float
    backend_s: float  # a compile, or a load from the persistent cache
    cache: Optional[str]  # "hit" | "miss" | None (the persistent cache is off, or kept no entry)
    nested_traces: int
    in_one_chunk: bool = False  # built below ``in_one_chunk``'s frame (a set-up), not on a serving thread's own stack
    # "hit": built from a module the program store held; "miss": traced, lowered, exported and written first (those
    # seconds are in trace_s and lower_s); None: today's path (no store, a mesh, an export that failed, an eager executable)
    store: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s

    def brief(self) -> dict:
        return {"kind": self.kind, "key": None if self.key is None else str(self.key), "fun_name": self.fun_name,
                "phase": self.phase, "trace_s": round(self.trace_s, 4), "lower_s": round(self.lower_s, 4),
                "backend_s": round(self.backend_s, 4), "cache": self.cache, "nested_traces": self.nested_traces,
                "in_one_chunk": self.in_one_chunk, "store": self.store}


class _Thread(threading.local):
    """What one thread's events have said since its last entry closed, and its open scopes."""

    def __init__(self) -> None:
        self.traces: "deque[tuple]" = deque(maxlen=BUILD_LOG_SIZE)  # (arrival ns, fun_name, seconds)
        self.lower: Optional[tuple] = None  # (fun_name, seconds)
        self.cache: Optional[str] = None
        self.store: Optional[str] = None  # where the program about to be built came from (``BuildLog.stored``)
        self.exported = (0.0, 0.0, 0)  # trace_s, lower_s, nested traces of the export a store miss made (``BuildLog.exported``)
        self.scopes: list = []  # open StepSpans, outermost first
        self.launch: Optional[tuple] = None  # (the newest sched.launch span, the key record_exec was handed)


class BuildLog:
    """Entries and scope intervals, bounded; ``total`` and ``total_ns`` only
    grow (a dispatch reads them before and after to know that it built).
    Events arrive on the thread that called the jitted function, so what is
    pending is kept a thread; closing an entry takes a lock."""

    def __init__(self, maxlen: int = BUILD_LOG_SIZE) -> None:
        self.entries: "deque[BuildEntry]" = deque(maxlen=maxlen)
        self.scopes: "deque[tuple]" = deque(maxlen=maxlen)  # closed scopes: (name, kind | None, t0_ns, t1_ns)
        self.total = 0
        self.total_ns = 0
        self.serving = False
        self._thread = _Thread()
        self._lock = threading.Lock()

    # --- jax.monitoring listeners ---------------------------------------------
    def on_duration(self, event: str, secs: float, fun_name: str = "", **_: Any) -> None:
        if event == TRACE_EVENT:
            self._thread.traces.append((time.monotonic_ns(), fun_name, secs))
        elif event == LOWER_EVENT:
            self._thread.lower = (fun_name, secs)
        elif event == BACKEND_EVENT:
            self._close(fun_name, secs)

    def on_event(self, event: str, **_: Any) -> None:
        cache = CACHE_EVENTS.get(event)
        if cache is not None:
            self._thread.cache = cache

    def _take_pending(self, fun_name: str) -> tuple:
        """(trace_s, lower_s, nested traces) of what this thread's events have
        said of ``fun_name`` ("jit(mixed_step)") since they were last taken,
        and forget them. "jit(mixed_step)" was traced as "mixed_step". Traces
        that arrive after the outer one ended belong to the lowering (a scan's
        condition)."""
        mine = self._thread
        lower_s = mine.lower[1] if mine.lower is not None and mine.lower[0] == fun_name else 0.0
        traced_as = fun_name[fun_name.find("(") + 1:-1] if fun_name.endswith(")") else fun_name
        trace_s, nested = 0.0, 0
        for t, name, secs in reversed(mine.traces):
            if name == traced_as:
                began = t - int(secs * 1e9)
                trace_s, nested = secs, sum(1 for other in mine.traces if other[0] >= began) - 1
                break
        mine.traces.clear()
        mine.lower = None
        return trace_s, lower_s, nested

    def where(self) -> tuple:
        """(kind, key, phase) of what this thread builds now: the innermost
        ``build.key`` scope, else the open ``sched.launch``, else eager."""
        mine = self._thread
        kind, key, phase = EAGER, None, None
        for span in reversed(mine.scopes):
            if span.name == KEY_SCOPE:
                if kind is EAGER:
                    kind, key = span.attrs["kind"], span.attrs["key"]
            elif phase is None:
                phase = span.name
        if kind is EAGER and mine.launch is not None and mine.launch[0].t0 and not mine.launch[0].t1:
            span, last = mine.launch
            kind = span.attrs["kind"]
            key = tuple(last[1:]) if last and last[0] == kind else ()
        if phase is None and self.serving:
            phase = SERVING
        return kind, key, phase

    def _close(self, fun_name: str, backend_s: float) -> None:
        now = time.monotonic_ns()
        mine = self._thread
        trace_s, lower_s, nested = self._take_pending(fun_name)
        trace_s, lower_s, nested = trace_s + mine.exported[0], lower_s + mine.exported[1], nested + mine.exported[2]
        cache, store = mine.cache, mine.store
        mine.cache = mine.store = None
        mine.exported = (0.0, 0.0, 0)
        kind, key, phase = self.where()
        entry = BuildEntry(now, threading.get_ident(), fun_name, phase, kind, key, trace_s, lower_s, backend_s, cache, nested,
                           _stack.anchored, store)
        with self._lock:
            self.entries.append(entry)
            self.total += 1
            self.total_ns += int(entry.seconds * 1e9)

    # --- the program store (engine/program_store.py) ------------------------------
    def stored(self, store: Optional[str]) -> None:
        """The next executable this thread builds is of a program that came
        from the store (``"hit"``), was written to it just now (``"miss"``), or
        takes today's path (None)."""
        self._thread.store = store

    def exported(self, fun_name: str) -> None:
        """A store miss has just traced and lowered ``fun_name`` to export it:
        those seconds go to the entry that closes next on this thread, beside
        what building from the exported module costs."""
        self._thread.exported = self._take_pending(fun_name)

    # --- scopes -----------------------------------------------------------------
    @contextmanager
    def scope(self, log, name: str, **attrs: Any) -> Iterator[Any]:
        """A ``StepSpan`` on ``log`` (so it is in the step log and, with a
        profiler session open, in the trace as ``dyn:<name>``) that is also
        this thread's innermost scope while it is open."""
        open_scopes = self._thread.scopes
        span = log.span(name, **attrs)
        open_scopes.append(span)
        try:
            with span:
                yield span
        finally:
            open_scopes.pop()
            self.scopes.append((name, attrs.get("kind"), span.t0, span.t1))

    def launching(self, span, last_exec: Optional[tuple]) -> None:
        """``span`` is the ``sched.launch`` this thread is about to open: while
        it is open, what is built here is of its ``kind`` and of the key
        ``record_exec`` was just handed (``last_exec``, where its kind is the span's)."""
        self._thread.launch = (span, last_exec)

    # --- what an operator reads (debug_state()["build"]) -------------------------
    def summary(self, since_ns: int = 0) -> dict:
        """Everything logged since ``since_ns``. Where an ``engine.build``
        scope began at ``since_ns`` (``TpuEngine.build``), the seconds by kind
        are those of the entries inside it and sum, with each kind's
        ``other_s``, to that scope: a kind's ``other_s`` is its ``build.key``
        scopes less the entries inside them (the warm-up dispatch's own run
        time, its read-back), the eager kind's is the scope less everything
        above (the pool, placement, Python that is neither tracing nor lowering)."""
        entries = [e for e in list(self.entries) if e.t_ns >= since_ns]
        scopes = [s for s in list(self.scopes) if s[2] >= since_ns]
        build = next((s for s in scopes if s[0] == BUILD and s[2] == since_ns), None)
        inside = entries
        if build is not None:
            inside = [e for e in entries if e.t_ns <= build[3]]
            scopes = [s for s in scopes if s[3] <= build[3]]
        by_kind: Dict[str, dict] = {}

        def tally(kind: str) -> dict:
            return by_kind.setdefault(kind, {"executables": 0, "nested_traces": 0, **dict.fromkeys(PARTS + ("other_s",), 0.0)})

        for e in inside:
            k = tally(e.kind)
            k["executables"] += 1
            k["nested_traces"] += e.nested_traces
            for part in PARTS:
                k[part] += getattr(e, part)
        keys = [s for s in scopes if s[0] == KEY_SCOPE]
        for _, kind, t0, t1 in keys:
            tally(kind)["other_s"] += (t1 - t0) / 1e9
        for kind, k in by_kind.items():
            if kind != EAGER:
                k["other_s"] -= sum(k[part] for part in PARTS)
        out: Dict[str, Any] = {
            "executables": len(entries),
            "keyed": sum(e.kind != EAGER for e in entries),
            "eager": sum(e.kind == EAGER for e in entries),
            "keys": len(keys),
            "cache_hits": sum(e.cache == "hit" for e in entries),
            "cache_misses": sum(e.cache == "miss" for e in entries),
            "in_one_chunk": sum(e.in_one_chunk for e in entries),
            "store_hits": sum(e.store == "hit" for e in entries),
            "store_misses": sum(e.store == "miss" for e in entries),
        }
        if build is not None:
            span_s = (build[3] - build[2]) / 1e9
            parts = {part: sum(k[part] for k in by_kind.values()) for part in PARTS}
            other_s = span_s - sum(parts.values())
            tally(EAGER)["other_s"] = other_s - sum(k["other_s"] for kind, k in by_kind.items() if kind != EAGER)
            out["engine_build"] = {"span_s": span_s, **parts, "other_s": other_s}
            out["phase_s"] = {s[0]: (s[3] - s[2]) / 1e9 for s in scopes if s[0] != KEY_SCOPE}
        out["by_kind"] = dict(sorted(by_kind.items()))
        out["costliest"] = [e.brief() for e in sorted(entries, key=lambda e: -e.seconds)[:TOP]]
        names: Dict[str, int] = {}
        for e in entries:
            if e.kind == EAGER:
                names[e.fun_name] = names.get(e.fun_name, 0) + 1
        out["eager_fun_names"] = dict(sorted(names.items(), key=lambda kv: -kv[1])[:TOP])
        out["since_warmup"] = [e.brief() for e in entries if e.phase == SERVING][-TOP:]
        return out


BUILD_LOG = BuildLog()
_listening = False


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache and ``BUILD_LOG`` at JAX's events
    (idempotent: a listener cannot be taken back, so each is registered once
    a process) and return the cache's directory."""
    import jax

    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(BUILD_LOG.on_duration)
        jax.monitoring.register_event_listener(BUILD_LOG.on_event)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR


def program_store_dir() -> Optional[str]:
    """Where the program store keeps its modules: ``programs/`` inside the
    persistent cache's directory, or None where that cache is off or has no
    directory yet (nothing called ``enable_compile_cache``)."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not (jax.config.jax_enable_compilation_cache and cache_dir):
        return None
    return os.path.join(cache_dir, "programs")
