"""The build log (PR 39, ``engine/compile_cache.py``): one entry an executable
JAX built, from JAX's own events, with the key of the scope it was built in.

(a) hand-made event sequences fed to a log of their own: the outer trace and
the nested ones, the lowering and the cache events, scopes and threads, the
open launch in serving, the bound, the summary's arithmetic; (b) one ``tiny``
engine built and warmed for the whole file (the gather path: no step program
under the Pallas interpreter): a keyed entry for every key warm-up registered,
the phases inside ``engine.build``, ``debug_state()["build"]`` summing to that
span, a warm stream that adds nothing, an unwarmed key that adds an entry and
``built`` on its ``sched.step``."""

import threading
import time

import jax
import pytest

from dynamo_tpu.engine import compile_cache
from dynamo_tpu.engine.compile_cache import BACKEND_EVENT, BUILD_LOG, LOWER_EVENT, TRACE_EVENT, BuildLog
from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig, StopConditions
from dynamo_tpu.runtime.tracing import StepLog

HIT, MISS = "/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses"


def feed(log, name, trace=0.03, lower=0.02, backend=0.01, helpers=(), in_lowering=(), cache=None, traced=True, lowered=True):
    """The events JAX sends for one executable of the jitted function ``name``:
    its helpers' traces, its own, the traces its lowering makes, the lowering,
    the cache's word, the backend."""
    for h in helpers:
        log.on_duration(TRACE_EVENT, 0.001, fun_name=h)
    if traced:
        log.on_duration(TRACE_EVENT, trace, fun_name=name)
    for h in in_lowering:
        log.on_duration(TRACE_EVENT, 0.0, fun_name=h)
    if lowered:
        log.on_duration(LOWER_EVENT, lower, fun_name=f"jit({name})")
    if cache:
        log.on_event(cache)
    log.on_duration(BACKEND_EVENT, backend, fun_name=f"jit({name})")
    return log.entries[-1]


# --- (a) hand-made events ----------------------------------------------------------------------------------------------


def test_an_entry_takes_the_outer_trace_and_counts_the_nested_ones():
    """The helpers' events come before their caller's, whose seconds hold
    theirs; a scan's condition is traced while the module is lowered."""
    log = BuildLog()
    e = feed(log, "mixed_step", trace=0.5, helpers=("cumsum", "sort", "add", "_where"), in_lowering=("less", "add"))
    assert (e.fun_name, e.trace_s, e.lower_s, e.backend_s) == ("jit(mixed_step)", 0.5, 0.02, 0.01)
    assert e.nested_traces == 6 and e.seconds == pytest.approx(0.53)
    assert (e.kind, e.key, e.phase, e.cache) == ("eager", None, None, None)
    assert log.total == 1 and log.total_ns == int(e.seconds * 1e9)
    # The next executable starts from nothing pending.
    assert feed(log, "decode", trace=0.25).nested_traces == 0


def test_traces_that_ended_before_the_outer_one_began_are_not_its_helpers():
    """``jax.eval_shape``, or a call that found its executable in memory,
    traces and builds nothing: what it left pending is no later entry's."""
    log = BuildLog()
    for stale in ("eval_shape_helper", "add", "decode"):
        log.on_duration(TRACE_EVENT, 0.001, fun_name=stale)
    time.sleep(0.005)
    e = feed(log, "decode", trace=0.002, helpers=("add",))
    assert e.trace_s == 0.002 and e.nested_traces == 1


@pytest.mark.parametrize("traced, lowered, want", [(True, True, (0.03, 0.02)), (False, True, (0.0, 0.02)),
                                                   (True, False, (0.03, 0.0)), (False, False, (0.0, 0.0))])
def test_what_jax_did_not_do_again_costs_the_entry_nothing(traced, lowered, want):
    """A jaxpr or a lowering that JAX kept in memory sends no event; another
    function's lowering left pending is not this executable's."""
    log = BuildLog()
    log.on_duration(LOWER_EVENT, 9.0, fun_name="jit(somebody_else)")
    e = feed(log, "prefill", traced=traced, lowered=lowered)
    assert (e.trace_s, e.lower_s, e.backend_s) == (*want, 0.01)


@pytest.mark.parametrize("event, want", [(HIT, "hit"), (MISS, "miss"), (None, None)])
def test_the_cache_says_hit_or_miss_or_nothing(event, want):
    log = BuildLog()
    assert feed(log, "decode", cache=event).cache == want
    assert feed(log, "decode").cache is None  # the word is the entry's that it preceded


def test_an_event_inside_build_key_carries_its_kind_and_key_and_one_outside_its_fun_name():
    log, steps = BuildLog(), StepLog()
    with log.scope(steps, "engine.build"):
        with log.scope(steps, "build.warmup"):
            zeros = feed(log, "broadcast_in_dim")  # an argument made before the key's scope opens
            with log.scope(steps, "build.key", kind="mixed", key=(256, 16, 32, 8)):
                step = feed(log, "mixed_step")
                with log.scope(steps, "build.key", kind="sampler", key=("sample", 32)):
                    inner = feed(log, "sample_batch")
        outside = feed(log, "convert_element_type")
    after = feed(log, "multiply")
    assert (zeros.kind, zeros.key, zeros.fun_name, zeros.phase) == ("eager", None, "jit(broadcast_in_dim)", "build.warmup")
    assert (step.kind, step.key, step.phase) == ("mixed", (256, 16, 32, 8), "build.warmup")
    assert (inner.kind, inner.key) == ("sampler", ("sample", 32))  # the innermost scope
    assert (outside.kind, outside.phase) == ("eager", "engine.build") and (after.kind, after.phase) == ("eager", None)
    # The scopes are spans of the step log too, and the log keeps their intervals itself.
    assert [s[0] for s in steps.spans] == ["build.key", "build.key", "build.warmup", "engine.build"]
    assert [(s[0], s[1]) for s in log.scopes] == [("build.key", "sampler"), ("build.key", "mixed"), ("build.warmup", None), ("engine.build", None)]
    assert steps.spans[1][4] == {"kind": "mixed", "key": (256, 16, 32, 8)}


def test_a_scope_that_raises_is_closed():
    log, steps = BuildLog(), StepLog()
    with pytest.raises(RuntimeError):
        with log.scope(steps, "build.key", kind="decode", key=(4, 8)):
            raise RuntimeError("the compiler refused")
    assert feed(log, "decode").kind == "eager" and len(log.scopes) == 1


def test_two_threads_scopes_and_pending_events_do_not_mix():
    """Events arrive on the thread that called the jitted function: each
    thread's traces, lowering and scopes are its own."""
    log, steps = BuildLog(), StepLog()
    barrier = threading.Barrier(2, timeout=20)
    seen = {}

    def worker(kind, program, helpers):
        with log.scope(steps, "build.key", kind=kind, key=(kind,)):
            for h in range(helpers):
                log.on_duration(TRACE_EVENT, 0.001, fun_name=f"helper{h}")
            log.on_duration(TRACE_EVENT, 0.5, fun_name=program)
            barrier.wait()  # both outer traces pending, on two threads
            log.on_duration(LOWER_EVENT, 0.1 * (helpers + 1), fun_name=f"jit({program})")
            barrier.wait()
            log.on_duration(BACKEND_EVENT, 0.01, fun_name=f"jit({program})")
            barrier.wait()
        seen[kind] = threading.get_ident()

    threads = [threading.Thread(target=worker, args=a) for a in (("decode", "decode", 3), ("prefill", "prefill", 7))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    by_kind = {e.kind: e for e in log.entries}
    assert set(by_kind) == {"decode", "prefill"} and log.total == 2
    assert (by_kind["decode"].nested_traces, by_kind["prefill"].nested_traces) == (3, 7)
    assert by_kind["decode"].lower_s == pytest.approx(0.4) and by_kind["prefill"].lower_s == pytest.approx(0.8)
    assert {k: e.thread for k, e in by_kind.items()} == seen and by_kind["decode"].key == ("decode",)


def test_in_serving_the_open_launch_is_the_scope():
    """``_launch`` hands the log its span, with the key ``record_exec`` was
    just handed: open, it names what is built inside; closed, nothing."""
    log, steps = BuildLog(), StepLog()
    log.serving = True
    span = steps.span("sched.launch", kind="mixed")
    log.launching(span, ("mixed", 256, 16, 32, 12))
    before = feed(log, "dynamic_slice")
    with span:
        inside = feed(log, "mixed_step")
    after = feed(log, "squeeze")
    assert [(e.kind, e.key, e.phase) for e in (before, inside, after)] == [
        ("eager", None, "serving"), ("mixed", (256, 16, 32, 12), "serving"), ("eager", None, "serving")]
    # A launch of another kind than the key last registered (a roll, a draft's prefill) has no key of its own.
    roll = steps.span("sched.launch", kind="eva_roll")
    log.launching(roll, ("mixed", 256, 16, 32, 12))
    with roll:
        assert (feed(log, "eva_roll").kind, log.entries[-1].key) == ("eva_roll", ())
    # ... and an explicit scope wins over the launch.
    with roll, log.scope(steps, "build.key", kind="calibrate", key=(4, 8)):
        assert feed(log, "decode").kind == "calibrate"


def test_the_log_is_bounded_and_its_counts_only_grow():
    log, steps = BuildLog(maxlen=8), StepLog()
    for i in range(20):
        with log.scope(steps, "build.key", kind="decode", key=(i,)):
            feed(log, "decode")
    assert len(log.entries) == 8 and len(log.scopes) == 8 and log.total == 20
    assert [e.key for e in log.entries] == [(i,) for i in range(12, 20)]
    assert log.total_ns == 20 * int(0.06 * 1e9)
    assert BuildLog().entries.maxlen == compile_cache.BUILD_LOG_SIZE >= 4096


def test_the_summary_of_a_hand_made_build_adds_up():
    log, steps = BuildLog(), StepLog()
    feed(log, "parity_forward")  # the harness's, before the engine's build: not in the summary
    with log.scope(steps, "engine.build") as build:
        with log.scope(steps, "build.scheduler"):
            feed(log, "broadcast_in_dim", trace=0.001, lower=0.002, backend=0.003)
        with log.scope(steps, "build.warmup"):
            for width in (4, 8):
                with log.scope(steps, "build.key", kind="decode", key=(32, width)):
                    feed(log, "decode", trace=0.2, lower=0.3, backend=0.1, helpers=("add",) * 5, cache=HIT)
                    time.sleep(0.01)  # the warm-up dispatch's own run time
            with log.scope(steps, "build.key", kind="decode", key=(32, 12)):
                pass  # built already (the calibration's): a key and no executable
    log.serving = True
    late = steps.span("sched.launch", kind="decode")
    log.launching(late, ("decode", 32, 16))
    with late:
        feed(log, "decode", trace=0.25, lower=0.3, backend=4.0, cache=MISS)
    s = log.summary(build.t0)
    assert (s["executables"], s["keyed"], s["eager"], s["keys"], s["cache_hits"], s["cache_misses"]) == (4, 3, 1, 3, 2, 1)
    eb = s["engine_build"]
    assert eb["span_s"] == pytest.approx(build.dur) and eb["span_s"] == s["phase_s"]["engine.build"]
    assert (eb["trace_s"], eb["lower_s"], eb["backend_s"]) == pytest.approx((0.401, 0.602, 0.203))
    assert eb["other_s"] == pytest.approx(build.dur - 1.206)
    assert set(s["phase_s"]) == {"engine.build", "build.scheduler", "build.warmup"}
    decode, eager = s["by_kind"]["decode"], s["by_kind"]["eager"]
    assert (decode["executables"], decode["nested_traces"], eager["executables"]) == (2, 10, 1)  # the late one is not the build's
    key_scopes = sum((t1 - t0) / 1e9 for name, _, t0, t1 in log.scopes if name == "build.key")
    assert decode["other_s"] == pytest.approx(key_scopes - 1.2) and decode["other_s"] >= 0.02 - 1.2
    assert sum(k[p] for k in s["by_kind"].values() for p in ("trace_s", "lower_s", "backend_s", "other_s")) == pytest.approx(eb["span_s"])
    assert s["costliest"][0]["key"] == "(32, 16)" and s["costliest"][0]["backend_s"] == 4.0 and len(s["costliest"]) == 4
    assert s["eager_fun_names"] == {"jit(broadcast_in_dim)": 1}
    assert [(e["kind"], e["key"], e["phase"]) for e in s["since_warmup"]] == [("decode", "(32, 16)", "serving")]
    # Without an engine.build scope at ``since_ns`` (a bare Scheduler): counts and kinds, no span to sum to.
    bare = log.summary(0)
    assert bare["executables"] == 5 and "engine_build" not in bare and bare["by_kind"]["decode"]["executables"] == 3


def test_a_second_enable_compile_cache_registers_nothing_twice(monkeypatch):
    from jax._src import monitoring

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/nonexistent/placed-from-outside")  # set nothing in jax.config
    for _ in range(3):
        compile_cache.enable_compile_cache()
    mine = lambda listeners, fn: sum(1 for cb in listeners if cb == fn)  # noqa: E731
    assert mine(monitoring._event_duration_secs_listeners, BUILD_LOG.on_duration) == 1
    assert mine(monitoring._event_listeners, BUILD_LOG.on_event) == 1


# --- (b) one tiny engine, built and warmed once ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def built():
    """(engine, BUILD_LOG.total before its build): ``tiny`` on the gather path,
    one chunk bucket, one batch bucket, windows of 4, tables of 4 blocks.
    JAX's in-memory caches are emptied first: a test file that served the same
    preset in this worker before (xdist hands files out as workers fall free)
    has left its step programs there, and this build would then build nothing."""
    jax.clear_caches()
    total0 = BUILD_LOG.total
    engine = TpuEngine.build(EngineArgs(
        model="tiny", dtype="float32", warmup_ctx=64,
        scheduler=SchedulerConfig(num_blocks=96, max_running=4, prefill_buckets=[16], decode_buckets=[4], max_prefill_chunk=16,
                                  mixed_prefill_budget=16, num_scheduler_steps=4, enable_prefix_caching=False)))
    return engine, total0


def serve(sched, arrivals):
    """``arrivals`` {iteration: [(id, prompt, max_tokens)]}, greedy, to the end: tokens a request."""
    out = {}
    for i in range(400):
        for rid, prompt, n in arrivals.get(i, ()):
            sched.add_request(rid, prompt, SamplingParams(temperature=0.0), StopConditions(max_tokens=n, ignore_eos=True))
        if i > max(arrivals) and not sched.has_work():
            break
        for seq, o in sched.step():
            if o.token_id >= 0:
                out[seq.request_id] = out.get(seq.request_id, 0) + 1
    assert not sched.has_work()
    return out


def test_warmup_leaves_a_keyed_entry_for_every_key_it_registered_and_the_phases_nest(built):
    engine, total0 = built
    flight = engine.scheduler.flight
    assert flight.builds is BUILD_LOG and flight.log.named("engine.build")[0][1] == flight.since_ns
    mine = [e for e in BUILD_LOG.entries if e.t_ns >= flight.since_ns]
    assert len(mine) == BUILD_LOG.total - total0 >= len(flight._exec_keys)
    keyed = {(e.kind, *e.key) for e in mine if e.kind != "eager"}
    # The decode executable of the first (bucket, width) is built by the cost model's calibration, which lowers and
    # compiles it to read XLA's own FLOP count: the warm-up call of that key then builds nothing.
    assert ("calibrate", 4, 4) in keyed and ("decode", 4, 4) in flight._exec_keys
    assert flight._exec_keys - {("decode", 4, 4)} <= keyed, flight._exec_keys - keyed
    # (The samplers are jits of module functions, which every Scheduler of the process shares: another test file's
    # engine may have built them already, and their scopes then hold no entry.)
    assert {"admit", "decode_multi", "mixed", "prefill"} <= {e.kind for e in mine}
    assert {"sampler", "calibrate"} <= {s[1] for s in BUILD_LOG.scopes if s[0] == "build.key" and s[2] >= flight.since_ns}
    step_programs = [e for e in mine if e.kind in ("decode", "decode_multi", "mixed", "prefill", "calibrate")]
    assert all(e.phase == "build.warmup" and e.trace_s > 0 and e.lower_s > 0 and e.nested_traces > 20 for e in step_programs)
    assert any(e.kind == "eager" and e.phase == "build.scheduler" for e in mine)  # the pool's zeros
    # engine.build > build.params, build.scheduler, build.warmup > build.key, in the step log as in the build log.
    spans = {name: (t0, t1) for name, t0, t1, _, _ in flight.log.spans if name.startswith(("engine.", "build.")) and name != "build.key"}
    b0, b1 = spans["engine.build"]
    assert b0 <= spans["build.params"][0] <= spans["build.params"][1] <= spans["build.scheduler"][0]
    assert spans["build.scheduler"][1] <= spans["build.warmup"][0] <= spans["build.warmup"][1] <= b1
    keys = flight.log.named("build.key")
    assert len(keys) >= len(flight._exec_keys) and all(spans["build.warmup"][0] <= t0 <= t1 <= spans["build.warmup"][1] for _, t0, t1, _, _ in keys)
    assert {s[:1] + s[2:] for s in BUILD_LOG.scopes if s[0] in spans and s[2] >= b0} == {(n, *iv) for n, iv in spans.items()}


def test_debug_state_build_sums_to_the_engine_build_span(built):
    engine, _ = built
    b = engine.debug_state()["build"]
    eb = b["engine_build"]
    name, t0, t1, _, _ = engine.scheduler.flight.log.named("engine.build")[0]
    assert eb["span_s"] == pytest.approx((t1 - t0) / 1e9) and eb["other_s"] > 0
    assert eb["trace_s"] + eb["lower_s"] + eb["backend_s"] + eb["other_s"] == pytest.approx(eb["span_s"])
    by_kind = b["by_kind"]
    assert sum(k[p] for k in by_kind.values() for p in ("trace_s", "lower_s", "backend_s", "other_s")) == pytest.approx(eb["span_s"])
    assert all(k["other_s"] >= 0 for k in by_kind.values())  # a key's scope holds its entries' seconds
    assert sum(k["executables"] for k in by_kind.values()) == b["executables"] == b["keyed"] + b["eager"]
    assert b["keys"] >= len(engine.scheduler.flight._exec_keys) and by_kind["calibrate"]["executables"] == 1
    assert set(b["phase_s"]) == {"engine.build", "build.params", "build.scheduler", "build.warmup"} and min(b["phase_s"].values()) > 0
    assert sum(b["phase_s"].values()) - eb["span_s"] <= eb["span_s"]  # the children lie inside engine.build
    assert 5 <= len(b["costliest"]) <= 10 and b["costliest"][0]["kind"] != "eager" and b["since_warmup"] == []
    assert sum(b["eager_fun_names"].values()) <= b["eager"]


def test_with_the_persistent_cache_off_an_entry_says_nothing_of_it(built):
    engine, _ = built
    assert not jax.config.jax_enable_compilation_cache  # tests/conftest.py
    mine = [e for e in BUILD_LOG.entries if e.t_ns >= engine.scheduler.flight.since_ns]
    assert mine and all(e.cache is None for e in mine)
    b = engine.debug_state()["build"]
    assert (b["cache_hits"], b["cache_misses"]) == (0, 0)


def test_a_stream_after_warmup_adds_no_entry_and_an_unwarmed_key_adds_one_with_its_key(built):
    engine, _ = built
    sched = engine.scheduler
    total0, step0 = BUILD_LOG.total, sched.flight.log.step
    # Two rows decode, a third joins them by a mixed step: tables of at most 4 blocks, as warmed.
    got = serve(sched, {0: [("a", list(range(1, 13)), 10)], 1: [("b", list(range(20, 29)), 10)], 3: [("c", list(range(40, 52)), 6)]})
    assert got == {"a": 10, "b": 10, "c": 6}
    steps = [a for name, _, _, step, a in sched.flight.log.spans if name == "sched.step" and step > step0 and a and "kind" in a]
    assert {"prefill", "mixed", "decode_multi"} <= {a["kind"] for a in steps}
    assert BUILD_LOG.total == total0 and not any("built" in a or "build_s" in a for a in steps)
    assert sched.flight.compiles_after_warmup_total == 0 and sched.debug_state()["build"]["since_warmup"] == []
    # A prompt of 70 tokens takes a table of 6 blocks: a prefill key that warm-up (ctx 64) never met.
    step1 = sched.flight.log.step
    assert serve(sched, {0: [("long", list(range(1, 71)), 3)]}) == {"long": 3}
    new = list(BUILD_LOG.entries)[total0 - BUILD_LOG.total:]
    assert new and all(e.phase == "serving" for e in new)
    unwarmed = [k for k in sched.flight.post_warmup_keys]
    assert unwarmed and {(e.kind, *e.key) for e in new if e.kind != "eager"} == set(unwarmed)
    built_steps = [a for name, _, _, step, a in sched.flight.log.spans if name == "sched.step" and step > step1 and a and "built" in a]
    assert built_steps and sum(a["built"] for a in built_steps) == len(new)
    assert sum(a["build_s"] for a in built_steps) == pytest.approx(sum(e.seconds for e in new), abs=1e-6)
    assert all(a["kind"] in {k[0] for k in unwarmed} for a in built_steps)
    since = sched.debug_state()["build"]["since_warmup"]
    assert [e["key"] for e in since if e["kind"] != "eager"] == [str(tuple(k[1:])) for k in unwarmed]


def test_every_entry_inside_engine_build_was_built_in_one_stack_chunk_and_none_in_serving_is(built):
    """PR 42: ``TpuEngine.build`` runs below ``compile_cache.in_one_chunk``'s frame; the step thread does not."""
    engine, _ = built
    sched = engine.scheduler
    flight = sched.flight
    _, t0, t1, _, _ = flight.log.named("engine.build")[0]
    inside = [e for e in BUILD_LOG.entries if t0 <= e.t_ns <= t1]
    assert len(inside) > 10 and all(e.in_one_chunk for e in inside)
    # Not the warm-up calls alone. (``build.params`` too, where no earlier test of the process has built the weights' programs.)
    assert {"build.scheduler", "build.warmup"} <= {e.phase for e in inside} <= {"build.params", "build.scheduler", "build.warmup"}
    # A prompt of 99 tokens takes a table wider than any that warm-up (ctx 64) or the tests above met.
    total0 = BUILD_LOG.total
    assert serve(sched, {0: [("odd", list(range(1, 100)), 2)]}) == {"odd": 2}
    new = list(BUILD_LOG.entries)[total0 - BUILD_LOG.total:] if BUILD_LOG.total > total0 else []
    assert new and all(e.phase == "serving" and not e.in_one_chunk for e in new)
    b = engine.debug_state()["build"]
    serving = [e for e in BUILD_LOG.entries if e.t_ns > t1 and e.t_ns >= flight.since_ns]
    assert b["in_one_chunk"] == len(inside) == b["executables"] - len(serving)
    assert all(e["in_one_chunk"] for e in b["costliest"] if e["phase"] != "serving") and not any(e["in_one_chunk"] for e in b["since_warmup"])
