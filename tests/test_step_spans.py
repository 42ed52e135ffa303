"""Step-phase spans and named step programs inside the engine (PR 25):
``runtime/tracing.py::StepLog`` / ``StepSpan``, the spans the scheduler, the
engine loop and the frontend write, the request records, the host gap read
from the log, and the names of the step programs' XLA modules."""

import asyncio
import time

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
from dynamo_tpu.engine.flight_recorder import RECENT_STEPS, FlightRecorder
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions
from dynamo_tpu.runtime import tracing
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.tracing import STEP_LOG_SIZE, StepLog

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
PHASES = ("sched.plan", "sched.upload", "sched.launch", "sched.sync", "sched.sample", "sched.emit",
          "sched.account")


def mk_sched(**kw) -> Scheduler:
    sc = dict(num_blocks=128, max_running=8, prefill_buckets=[16, 32], decode_buckets=[1, 2, 4, 8],
              num_scheduler_steps=1, enable_prefix_caching=False)
    sc.update(kw)
    return Scheduler(CFG, PARAMS, SchedulerConfig(**sc), dtype=jnp.float32)


def add(sched, rid, prompt, max_tokens, **sampling):
    sched.add_request(rid, prompt, SamplingParams(temperature=0.0, **sampling),
                      StopConditions(max_tokens=max_tokens, ignore_eos=True))


def drain(sched, late=()):
    """Run to completion; ``late`` requests join after two iterations, so
    they meet running sequences (a mixed step) instead of an empty engine."""
    late = list(late)
    for i in range(2000):
        if i == 2:
            for args in late:
                add(sched, *args)
        if not sched.has_work():
            break
        sched.step()
    assert not sched.has_work()


# Each case dispatches one step path: (scheduler settings, first requests, late requests, kind).
PATHS = {
    "decode": (dict(), [("a", list(range(1, 20)), 6)], [], "decode"),
    "decode_multi": (dict(num_scheduler_steps=8), [("a", list(range(1, 20)), 12)], [], "decode_multi"),
    "prefill": (dict(), [("a", list(range(1, 40)), 3)], [], "prefill"),
    "mixed": (dict(), [("a", list(range(1, 20)), 12)], [("b", list(range(30, 70)), 4)], "mixed"),
    "admit": (dict(), [("a", list(range(1, 12)), 3), ("b", list(range(20, 30)), 3)], [], "admit"),
}


def steps_with_children(log):
    """[(sched.step entry, its direct phase entries)] by interval containment."""
    spans = list(log.spans)
    out = []
    for st in (e for e in spans if e[0] == "sched.step"):
        inside = [e for e in spans if e[0] in PHASES and e[1] >= st[1] and e[2] <= st[2]]
        top = [e for e in inside
               if not any(o is not e and o[1] <= e[1] and e[2] <= o[2] and (o[2] - o[1]) > (e[2] - e[1])
                          for o in inside)]
        out.append((st, sorted(top, key=lambda e: e[1])))
    return out


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_step_path_writes_a_step_with_its_kind_and_phases_partition_it(path, monkeypatch):
    settings, first, late, kind = PATHS[path]
    sched = mk_sched(**settings)
    # A tiny model's step on the CPU takes 1-5 ms, of which the dozen span boundaries are a few per
    # cent under load; a dispatch on the chip takes 70-350 ms. Give every launch 50 ms, so that the
    # 2% below is judged at a dispatch's real scale.
    consume = sched._consume_aux
    monkeypatch.setattr(sched, "_consume_aux", lambda res: (time.sleep(0.05), consume(res))[1])
    for args in first:
        add(sched, *args)
    drain(sched, late)
    log = sched.flight.log
    steps = steps_with_children(log)
    assert steps and log.step == len(steps)
    kinds = {st[4]["kind"] for st, _ in steps if st[4]}
    assert kind in kinds, kinds
    covered = total = 0
    for st, top in steps:
        dur = st[2] - st[1]
        assert [e[3] for e in top] == [st[3]] * len(top)  # every phase carries its step's number
        for a, b in zip(top, top[1:]):
            assert a[2] <= b[1]  # phases follow each other: they nest under the step and never overlap
        own = sum(e[2] - e[1] for e in top)
        assert dur - own <= max(0.02 * dur, 100_000), (path, st, top)  # 2%; 0.1 ms for an iteration that launched nothing
        covered, total = covered + own, total + dur
        if st[4]:  # an iteration that dispatched: launch, and what the exec/done marks carried
            assert {"kind", "key", "rows", "ctx", "prefill", "decode"} <= set(st[4])
            assert any(e[0] == "sched.launch" for e in top)
    assert covered >= 0.98 * total
    dispatched = [st for st, _ in steps if st[4] and st[4]["kind"] == kind]
    names = {e[0] for st, top in steps if st in dispatched for e in top}
    spans = {e[0] for e in log.spans}
    assert {"sched.plan", "sched.launch", "sched.emit", "sched.account"} <= spans
    if path != "prefill":
        assert "sched.sync" in spans  # a blocking read-back, alone or nested under sched.sample
    if path in ("decode", "mixed", "admit", "prefill"):
        assert "sched.sample" in spans  # sampling is its own program on these paths
        syncs = [e for e in log.spans if e[0] == "sched.sync"]
        samples = [e for e in log.spans if e[0] == "sched.sample"]
        assert any(s[1] <= y[1] and y[2] <= s[2] for y in syncs for s in samples)  # nested sync
    if path in ("decode_multi", "decode", "mixed", "admit"):
        assert "sched.upload" in names


def test_spec_path_is_not_dark():
    sched = mk_sched()
    sched.attach_draft(CFG, PARAMS, gamma=2)
    add(sched, "a", list(range(1, 20)), 8)
    drain(sched)
    kinds = {e[4]["kind"] for e in sched.flight.log.spans if e[0] == "sched.step" and e[4]}
    assert "spec" in kinds
    launched = {e[4]["kind"] for e in sched.flight.log.spans if e[0] == "sched.launch"}
    assert {"spec_draft_chunk", "spec_target_chunk", "spec_verify"} <= launched


def test_flight_records_and_recent_steps_keep_their_shape():
    sched = mk_sched()
    for i in range(3):
        add(sched, f"r{i}", list(range(1 + i, 30 + i)), 30)
    drain(sched)
    for recent in (sched.debug_state()["flight"]["recent_steps"], sched.flight.ring_snapshot()["recent_steps"]):
        assert 0 < len(recent) <= RECENT_STEPS
        assert all(set(r) - {"chunk_attn"} == {"age_s", "phase", "dur_s", "tokens"} for r in recent)
        assert all(("chunk_attn" in r) == (r["phase"] in ("prefill", "mixed")) for r in recent)
        assert {r["phase"] for r in recent} <= {"prefill", "decode", "mixed", "wave", "spec"}
        assert all(r["age_s"] >= 0 and r["dur_s"] >= 0 and isinstance(r["tokens"], int) for r in recent)
        assert [r["age_s"] for r in recent] == sorted((r["age_s"] for r in recent), reverse=True)  # oldest first
    # A bare recorder (no scheduler, as the metric tests use it) serves the same ring.
    fr = FlightRecorder()
    for i in range(RECENT_STEPS + 6):
        fr.record_step("decode", 0.004, 8)
    fr.record_mixed_step(0.01, prefill_tokens=128, decode_tokens=8)
    recent = fr.recent_steps()
    assert len(recent) == RECENT_STEPS and recent[-1] == {**recent[-1], "phase": "mixed", "tokens": 136}
    assert recent[0]["dur_s"] == pytest.approx(0.004, abs=1e-6) and fr.last_step_phase == "mixed"
    assert abs(time.monotonic() - fr.last_step_ts) < 1.0  # the stall watchdog's clock


@pytest.mark.parametrize("impl,path", [("gather", "gather"), ("paged", "tile32"), ("megakernel", "tile32")])
def test_a_chunk_carrying_step_names_the_chunks_attention_path(impl, path):
    """``sched.step`` of a dispatch that carried a prefill chunk says how the
    chunk met its keys in the program as traced — a bare chunk, a mixed step,
    and a chunk that follows a decode dispatch in its iteration — and
    ``recent_steps`` of /debug/state shows it. Wherever a kernel serves the
    pool the chunk walks tiles (``llama.chunk_walks_tiles``): ``paged`` names
    the decode rows' kernel, not the chunk's."""
    sched = Scheduler(CFG.replace(attention_impl=impl), PARAMS,
                      SchedulerConfig(num_blocks=128, max_running=8, prefill_buckets=[32], decode_buckets=[4],
                                      num_scheduler_steps=1, enable_prefix_caching=False), dtype=jnp.float32)
    add(sched, "a", list(range(1, 40)), 12)
    drain(sched, late=[("b", list(range(30, 100)), 4)])
    steps = [e[4] for e in sched.flight.log.spans if e[0] == "sched.step" and e[4]]
    carried = [a for a in steps if a["prefill"] or a["kind"] in ("prefill", "mixed")]
    assert {a["kind"] for a in carried} >= {"prefill", "mixed"}
    assert carried and all(a["chunk_attn"] == path for a in carried)
    assert all("chunk_attn" not in a for a in steps if a not in carried and a.get("dispatches", 1) == 1)
    recent = sched.debug_state()["flight"]["recent_steps"]
    assert {r["chunk_attn"] for r in recent if r["phase"] in ("prefill", "mixed")} == {path}
    assert all("chunk_attn" not in r for r in recent if r["phase"] == "decode")


@pytest.mark.parametrize("path", ["decode", "decode_multi", "mixed"])
def test_a_step_with_decode_rows_counts_its_attention_items_beside_its_slots(path, monkeypatch):
    """``attn_items`` on a ``sched.step`` entry is the count the rows' launch
    reads off the dispatch's own operands (``megakernel.build_work`` on the
    packed rows and the uploaded tables' width, at the pages a step the
    engine's launch takes): a step a group of pages under a live row's current
    token; ``attn_pages`` counts those pages (the list's count at a page a
    step); ``attn_slots`` is the bucket x (table width + 1) the static grid
    spanned. ``debug_state`` sums all three."""
    from dynamo_tpu.engine.attention import megakernel as mk

    settings, first, late, kind = PATHS[path]
    sched = Scheduler(CFG.replace(attention_impl="megakernel"), PARAMS,
                      SchedulerConfig(**{**dict(num_blocks=128, max_running=8, prefill_buckets=[16, 32], decode_buckets=[4, 8],
                                                num_scheduler_steps=1, enable_prefix_caching=False), **settings}),
                      dtype=jnp.float32)
    bs = CFG.block_size
    packed, pack, tables_of = [], sched._pack_rows, sched._decode_tables
    monkeypatch.setattr(sched, "_pack_rows", lambda batch, bucket, **kw: (packed.append(pack(batch, bucket, **kw)), packed[-1])[1])
    monkeypatch.setattr(sched, "_decode_tables", lambda batch, bucket, width: (
        packed.append(width), tables_of(batch, bucket, width))[1])
    add(sched, "a", list(range(1, 20)), 12)
    add(sched, "b", list(range(1, 40)), 9)  # a longer row: its pages set the table's width
    drain(sched, late)
    steps = [e[4] for e in sched.flight.log.spans if e[0] == "sched.step" and e[4] and "attn_items" in e[4]]
    assert kind in {a["kind"] for a in steps} and {a["kind"] for a in steps} <= {"decode", "decode_multi", "mixed"}
    assert all("attn_items" in e[4] for e in sched.flight.log.spans
               if e[0] == "sched.step" and e[4] and e[4].get("kind") in ("decode", "decode_multi", "mixed"))
    operands = list(zip(packed[0::2], packed[1::2]))  # (rows, width) of every dispatch that carried decode rows, in order
    assert len(operands) == len(steps)
    for attrs, (rows, width) in zip(steps, operands):
        bucket = rows.shape[1]
        per_step = llama.rows_pages_per_step(CFG, sched.cache.k, width)
        assert per_step == mk.pages_per_step(bs, CFG.num_kv_heads * CFG.head_dim, 4, width) > 1  # pages of 2 KB
        prefixes, live = jnp.minimum(rows[1], width * bs), rows[2] > 0
        work, a_page_a_step = mk.build_work(prefixes, live, width, bs, per_step), mk.build_work(prefixes, live, width, bs)
        assert attrs["attn_items"] == int(work[0]) and attrs["attn_pages"] == int(a_page_a_step[0])
        assert attrs["attn_slots"] == bucket * (width + 1) == a_page_a_step.shape[0] - 1
        assert attrs["rows"] <= attrs["attn_items"] <= attrs["attn_pages"] <= attrs["attn_slots"]
    assert any(a["attn_items"] < a["attn_slots"] // 2 for a in steps)  # two rows in a bucket of 4: most of the span is dead
    assert any(a["attn_items"] < a["attn_pages"] for a in steps)  # a row of 3 pages is 2 steps at 2 pages a step, 1 at 4
    state = sched.debug_state()
    assert state["attn_items_total"] == sum(a["attn_items"] for a in steps)
    assert state["attn_pages_total"] == sum(a["attn_pages"] for a in steps)
    assert state["attn_slots_total"] == sum(a["attn_slots"] for a in steps)
    gather = mk_sched()
    add(gather, "a", list(range(1, 20)), 4)
    drain(gather)
    assert gather.debug_state()["attn_items_total"] == 0  # the gather path launches no such walk


@pytest.mark.parametrize("kv_heads,carried", [(8, 1.0), (2, 3.5)], ids=["1024-lanes", "256-lanes"])
def test_pages_a_step_of_the_rows_launch_follow_the_pages_lanes(kv_heads, carried):
    """``attn_pages / attn_items`` is the pages a step of the rows' launch
    carried: 1.0 where a page of 64 float32 tokens is 1,024 lanes wide (256
    KB a side: ``pages_per_step`` 1), 3.5 over 256 lanes (64 KB: 4 pages a
    step; rows of 3 and 4 pages are a step each)."""
    cfg = CFG.replace(num_heads=kv_heads, num_kv_heads=kv_heads, head_dim=128, block_size=64, attention_impl="megakernel")
    params = llama.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    sched = Scheduler(cfg, params, SchedulerConfig(num_blocks=16, max_running=4, prefill_buckets=[256], decode_buckets=[2],
                                                   num_scheduler_steps=1, enable_prefix_caching=False), dtype=jnp.float32)
    add(sched, "a", [1 + i % 200 for i in range(150)], 3)
    add(sched, "b", [2 + i % 200 for i in range(240)], 3)
    drain(sched)
    steps = [e[4] for e in sched.flight.log.spans if e[0] == "sched.step" and e[4] and e[4].get("kind") == "decode"]
    both = [a for a in steps if a["rows"] == 2]
    assert both and all(a["attn_pages"] == 7 and a["attn_pages"] / a["attn_items"] == carried for a in both)
    state = sched.debug_state()
    assert state["attn_pages_total"] == sum(a["attn_pages"] for a in steps if "attn_pages" in a) >= 7
    assert (state["attn_pages_total"] == state["attn_items_total"]) == (carried == 1.0)


def test_host_gap_is_read_from_the_logs_launch_stamps():
    sched = mk_sched()
    add(sched, "a", list(range(1, 20)), 10)
    drain(sched, late=[("b", list(range(30, 70)), 3)])
    launches = [e for e in sched.flight.log.spans if e[0] == "sched.launch"]
    expect = sum(1 for a, b in zip(launches, launches[1:]) if a[4].get("decode") and b[4].get("decode"))
    stats = sched.flight.to_stats()
    assert expect > 0 and stats["decode_host_gap_events_total"] == expect
    assert stats["decode_host_gap_seconds_total"] > 0
    assert any(not e[4].get("decode") for e in launches[1:])  # the mixed step and the prefill broke the chain


def test_log_is_bounded_and_sized_for_a_minute_of_a_saturated_engine():
    log = StepLog(maxlen=8, request_maxlen=2)
    for i in range(100):
        with log.span("x", i=i):
            pass
        log.requests.append({"request_id": i})
    assert len(log.spans) == 8 and log.spans[-1][4] == {"i": 99} and len(log.requests) == 2
    assert STEP_LOG_SIZE >= 16000 and FlightRecorder().log.spans.maxlen == STEP_LOG_SIZE
    assert log.last("x")[4] == {"i": 99} and log.last("y") is None and len(log.named("x", 3)) == 3


class Loud:
    """An attribute value that counts every attempt to turn it into text."""

    calls = 0

    def __str__(self):
        Loud.calls += 1
        return "loud"

    __repr__ = __str__

    def __format__(self, spec):
        Loud.calls += 1
        return "loud"


class StubAnnotation:
    made = []

    def __init__(self, name, **kw):
        StubAnnotation.made.append((name, kw))

    def __enter__(self):
        return self

    def __exit__(self, *a):
        StubAnnotation.made.append(("exit", {}))

    def set_metadata(self, **kw):
        StubAnnotation.made.append(("set", kw))


def test_without_a_profiler_session_a_span_formats_nothing(monkeypatch):
    Loud.calls, StubAnnotation.made = 0, []
    log = StepLog()
    monkeypatch.setattr(tracing, "_profiler", (lambda: False, StubAnnotation))
    with log.span("sched.step", key=Loud()) as s:
        s.set(kind=Loud())
    assert StubAnnotation.made == [] and Loud.calls == 0  # one branch, no annotation, no text
    name, t0, t1, step, attrs = log.spans[-1]
    assert name == "sched.step" and t1 >= t0 and set(attrs) == {"key", "kind"} and s.dur == (t1 - t0) / 1e9
    # With a session open the same call site writes the profiler's span too: prefixed name, the
    # attributes as keyword arguments (the profiler formats them, not the program).
    monkeypatch.setattr(tracing, "_profiler", (lambda: True, StubAnnotation))
    log.step = 7
    with log.span("sched.step", rows=3) as s:
        s.set(kind="mixed")
    assert StubAnnotation.made == [("dyn:sched.step", {"step": 7, "rows": 3}), ("set", {"kind": "mixed"}),
                                   ("exit", {})]
    assert Loud.calls == 0 and log.spans[-1][3:] == (7, {"rows": 3, "kind": "mixed"})


def test_spans_reach_a_real_profiler_trace(tmp_path):
    import glob

    from jax.profiler import ProfileData

    log = StepLog()
    log.step = 3
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with log.span("sched.step") as s:
            s.set(kind="decode_multi", key=(8, 32, 16))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = [dict(ev.stats) for plane in ProfileData.from_file(path).planes for line in plane.lines
             for ev in line.events if ev.name == "dyn:sched.step"]
    assert found == [{"step": 3, "kind": "decode_multi", "key": "(8, 32, 16)"}]


def test_step_programs_are_named_after_their_kind():
    sched = mk_sched(num_scheduler_steps=8, enable_prefix_caching=True)
    k, v, p = sched.cache.k, sched.cache.v, sched.params
    b, w = 2, 4
    i32 = jnp.int32
    pos, tables = jnp.zeros((b,), i32), jnp.zeros((b, w), i32)
    temps, tks, tps = jnp.zeros((b,), jnp.float32), jnp.zeros((b,), i32), jnp.ones((b,), jnp.float32)
    key = jax.random.PRNGKey(0)
    # The packed operands of a dispatch (scheduler.pack_operands): a batch's three lanes (a window's six and its key); a
    # chunk's tokens, its length and its start; in a mixed step the chunk, the batch's lanes and the chunk's table.
    chunk, ptab = jnp.zeros((16 + 2,), i32), jnp.zeros((16,), i32)
    programs = {
        "prefill": (sched._prefill_jit, (p, k, v, chunk, ptab)),
        "decode": (sched._decode_jit, (p, k, v, jnp.zeros((3 * b,), i32), tables)),
        "decode_multi_w8": (sched._decode_multi_jits[8], (p, k, v, jnp.zeros((6 * b + 2,), i32), tables)),
        "mixed_step": (sched._get_mixed_jit((16, 16, b, w)),
                       (p, k, v, jnp.zeros((16 + 2 + 3 * b + 16,), i32), tables)),
        "admit_wave": (sched._get_admit_jit((b, 16, w)),
                       (p, k, v, jnp.zeros((b, 16), i32), pos, pos, tables)),
        "kv_block_copy": (sched._kv_copy_jit, (k, v, i32(0), i32(0))),
        "sample_batch": (sched._sample_jit, (jnp.zeros((b, CFG.vocab_size)), temps, tks, tps, key, None)),
    }
    assert sorted(sched._decode_multi_jits) == [8]
    for name, (fn, args) in programs.items():
        text = fn.lower(*args).as_text()
        assert f"module @jit_{name} " in text, (name, text[:120])
        assert "jit__lambda" not in text
    sched.attach_draft(CFG, PARAMS, gamma=3)
    for name, fn in (("draft_prefill", sched._d_prefill_jit), ("spec_draft_chunk", sched._d_chunk_sample_jit),
                     ("spec_target_chunk", sched._t_chunk_jit), ("spec_draft_multi_w2", sched._d_multi_jit),
                     ("spec_verify", sched._spec_verify_jit)):
        assert fn.__wrapped__.__name__ == name


async def test_request_record_orders_its_stamps_and_measures_the_staged_wait():
    engine = TpuEngine.build(EngineArgs(
        model="tiny", dtype="float32",
        scheduler=SchedulerConfig(num_blocks=64, prefill_buckets=[16, 32], decode_buckets=[1, 2, 4],
                                  num_scheduler_steps=1)))
    real_step = engine.scheduler.step

    def slow_step():
        time.sleep(0.05)  # a dispatch that holds the step thread, as a decode window holds it on the chip
        return real_step()

    engine.scheduler.step = slow_step

    async def one(rid, start, max_tokens, delay=0.0):
        await asyncio.sleep(delay)
        req = {"token_ids": list(range(start, start + 20)), "sampling_options": {"temperature": 0},
               "stop_conditions": {"max_tokens": max_tokens, "ignore_eos": True}}
        return [t async for f in engine.generate(req, Context(id=rid)) for t in f.get("token_ids") or []]

    try:
        # "late" is handed to the engine while a step of "first" is running: it sits staged until
        # that dispatch returns.
        a, b = await asyncio.gather(one("first", 1, 6), one("late", 40, 4, delay=0.07))
        assert (len(a), len(b)) == (6, 4)
    finally:
        await engine.stop()
    log = engine.scheduler.flight.log
    recs = {r["request_id"]: r for r in log.requests}
    assert set(recs) == {"first", "late"}
    for r in recs.values():
        assert r["enqueued"] <= r["arrival"] <= r["admitted"] <= r["first_token"] <= r["finished"]
        assert r["reason"] == "length" and r["prompt_tokens"] == 20 and r["prefill_chunks"] >= 1
        assert 1 <= r["first_step"] <= r["last_step"] <= log.step and r["preemptions"] == 0
    assert recs["first"]["output_tokens"] == 6 and recs["late"]["output_tokens"] == 4
    staged = {rid: r["arrival"] - r["enqueued"] for rid, r in recs.items()}
    assert staged["first"] < 0.02 < staged["late"], staged  # idle engine: at once; busy: the rest of the step (it compiles)
    # The engine loop's spans carry the step they frame.
    by_step = {}
    for e in log.spans:
        by_step.setdefault(e[3], set()).add(e[0])
    assert {"engine.loop", "engine.stage", "sched.step", "engine.deliver"} <= by_step[recs["late"]["first_step"]]
    loops = [e for e in log.spans if e[0] == "engine.loop"]
    steps = {e[3]: e for e in log.spans if e[0] == "sched.step"}
    assert all(lp[1] <= steps[lp[3]][1] and steps[lp[3]][2] <= lp[2] for lp in loops)  # parent of the step


async def test_frontend_frames_write_spans_to_the_process_log():
    from dynamo_tpu.llm.backend import Backend
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    async def frames():
        yield {"token_ids": [104, 105], "finish_reason": None, "index": 0}
        yield {"token_ids": [33], "finish_reason": "length", "index": 0}

    n0 = len(tracing.get_step_log().named("backend.frame"))
    out = [f async for f in Backend(ByteTokenizer()).transform_response(frames(), {}, Context())]
    assert len(out) == 2
    new = tracing.get_step_log().named("backend.frame")[n0:]
    assert [e[4] for e in new] == [{"tokens": 2}, {"tokens": 1}]
