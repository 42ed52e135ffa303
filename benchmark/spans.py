"""The benchmark's own instrumentation, wrapped around the program's objects
from outside (no program file changes): what JAX builds, the scheduler's
dispatches, the engine's first token per request.

``CompileMeter`` is ``chip_smoke.py::CompileMeter``, copied, with a time on
every event so that builds inside the measured window can be counted.
``Hooks`` replaces bound methods on one engine's instances with wrappers that
call through. With ``annotate`` the wrappers also write marks into the
profiler's trace (``jax.profiler.TraceAnnotation``, names ``bench:...``), so
the device's operations and the host's spans share a clock; without it they
only keep small in-memory logs.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple


class CompileMeter:
    """Counts what JAX really builds: every executable (compiled or fetched
    from the persistent cache) and the seconds it took."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.builds: List[Tuple[float, float]] = []  # (monotonic time, seconds)
        self.hits: List[float] = []
        self.misses: List[float] = []
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds.append((time.monotonic(), secs))

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.monotonic())
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses.append(time.monotonic())

    @property
    def executables(self) -> int:
        return len(self.builds)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.builds)

    def last_build(self) -> float:
        return self.builds[-1][0] if self.builds else 0.0

    def last_miss(self) -> float:
        return self.misses[-1] if self.misses else 0.0

    def in_window(self, t0: float, t1: float) -> dict:
        b = [(t, s) for t, s in self.builds if t0 <= t <= t1]
        return {"builds": len(b), "seconds": sum(s for _, s in b),
                "cache_hits": sum(t0 <= t <= t1 for t in self.hits),
                "cache_misses": sum(t0 <= t <= t1 for t in self.misses)}

    def snapshot(self) -> dict:
        return {"executables": self.executables, "compile_seconds": self.seconds,
                "cache_hits": len(self.hits), "cache_writes": len(self.misses)}


def prompt_key(token_ids) -> tuple:
    """Joins a client's request to the engine's stream: the six tokens after
    the role marker (random words of a >=32k vocabulary, so unique in a run)."""
    return tuple(int(t) for t in token_ids[1:7])


class Hooks:
    def __init__(self, engine, annotate: bool):
        self.engine = engine
        self.annotate = annotate
        self.observations: List[Tuple[float, str, float]] = []  # telemetry.observe
        self.dispatches: List[Tuple[float, str, tuple, int]] = []  # record_exec: time, kind, key, rows
        self.first_token: Dict[tuple, float] = {}  # prompt_key -> monotonic time
        self._install()

    def _mark(self, text: str):
        import jax

        return jax.profiler.TraceAnnotation("bench:" + text)

    def point(self, text: str) -> None:
        if self.annotate:
            with self._mark(text):
                pass

    def _install(self) -> None:
        sched = self.engine.scheduler
        flight, telemetry = sched.flight, sched.telemetry
        hooks = self

        step = sched.step

        def step_wrapped():
            if not hooks.annotate:
                return step()
            with hooks._mark("span|scheduler.step"):
                return step()

        sched.step = step_wrapped

        record_exec = flight.record_exec

        def record_exec_wrapped(kind, key):
            running = sched.running
            hooks.dispatches.append((time.monotonic(), kind, tuple(key), len(running)))
            if hooks.annotate:  # the mark's text is host work that only a traced run reads
                ctx = sum(s.total_len for s in running)
                hooks.point(f"exec|{kind}|{','.join(str(k) for k in key)}|rows={len(running)}|ctx={ctx}")
            return record_exec(kind, key)

        flight.record_exec = record_exec_wrapped

        record_step = flight.record_step

        def record_step_wrapped(phase, dur_s, tokens, kv_read_tokens=0, param_passes=1.0):
            if hooks.annotate:
                hooks.point(f"done|{phase}|tokens={tokens}|kv={kv_read_tokens}|passes={param_passes}|dur={dur_s}")
            return record_step(phase, dur_s, tokens, kv_read_tokens=kv_read_tokens, param_passes=param_passes)

        flight.record_step = record_step_wrapped

        record_mixed = flight.record_mixed_step

        def record_mixed_wrapped(dur_s, prefill_tokens, decode_tokens, *a, **kw):
            if hooks.annotate:
                hooks.point(f"done|mixed|tokens={prefill_tokens + decode_tokens}|prefill={prefill_tokens}"
                            f"|decode={decode_tokens}|dur={dur_s}")
            return record_mixed(dur_s, prefill_tokens, decode_tokens, *a, **kw)

        flight.record_mixed_step = record_mixed_wrapped

        observe = telemetry.observe

        def observe_wrapped(name, value):
            hooks.observations.append((time.monotonic(), name, float(value)))
            return observe(name, value)

        telemetry.observe = observe_wrapped

        generate = self.engine.generate

        async def generate_wrapped(request, context):
            key = prompt_key(request["token_ids"])
            async for frame in generate(request, context):
                if key not in hooks.first_token and frame.get("token_ids"):
                    hooks.first_token[key] = time.monotonic()
                yield frame

        self.engine.generate = generate_wrapped
