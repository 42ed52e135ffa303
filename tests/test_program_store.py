"""The program store (PR 48, ``engine/program_store.py``): a set-up's step
programs as exported modules beside JAX's persistent cache.

Tier-1 keeps the persistent cache off (``conftest.py``) and with it the store;
here a temporary cache directory is turned on for this file's tests alone and
turned off again after each. "A fresh process" is ``jax.clear_caches()``: what
a second build then finds is what is on disk.
"""

import logging
import os

import jax
import pytest

from dynamo_tpu.engine import program_store
from dynamo_tpu.engine.compile_cache import BUILD_LOG, program_store_dir
from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
from dynamo_tpu.engine.program_store import GENERATIONS, MAGIC, SUFFIX, ProgramStore, StoredJit
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import SchedulerConfig, StopConditions
from dynamo_tpu.engine.sharding import ParallelConfig

MODELS = ("tiny", "tiny-moe", "tiny-eva")
STEP_KINDS = ("calibrate", "decode", "decode_multi", "prefill", "mixed", "eva_roll")  # programs that hold a model's forward pass
CACHE_OPTIONS = {"jax_enable_compilation_cache": True, "jax_persistent_cache_min_compile_time_secs": 0.0,
                 "jax_persistent_cache_min_entry_size_bytes": -1}


def reset_cache():
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


@pytest.fixture
def cache_on(tmp_path_factory, monkeypatch):
    """JAX's persistent cache on, in a directory of this test's own; off and forgotten afterwards."""
    directory = str(tmp_path_factory.mktemp("jax_cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", directory)  # (``enable_compile_cache`` then leaves the directory alone)
    before = {name: getattr(jax.config, name) for name in (*CACHE_OPTIONS, "jax_compilation_cache_dir")}
    reset_cache()
    for name, value in {**CACHE_OPTIONS, "jax_compilation_cache_dir": directory}.items():
        jax.config.update(name, value)
    try:
        yield directory
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        reset_cache()
        jax.clear_caches()  # (no later test file finds this one's wrappers in memory)


def build(model, seed=0, parallel=None, warmup_ctx=64):
    """``model`` built and warmed as a fresh process would: (engine, its keyed entries, its summary)."""
    jax.clear_caches()
    engine = TpuEngine.build(EngineArgs(
        model=model, dtype="float32", warmup_ctx=warmup_ctx, seed=seed, parallel=parallel,
        scheduler=SchedulerConfig(num_blocks=96, max_running=4, prefill_buckets=[16], decode_buckets=[4], max_prefill_chunk=16,
                                  mixed_prefill_budget=16, num_scheduler_steps=4, enable_prefix_caching=False)))
    since = engine.scheduler.flight.since_ns
    keyed = [e for e in BUILD_LOG.entries if e.t_ns >= since and e.kind != "eager"]
    return engine, keyed, BUILD_LOG.summary(since)


def serve(sched, requests):
    """``requests`` [(id, prompt, max_tokens)], all at once, greedy, to the end: each one's tokens."""
    out = {rid: [] for rid, _, _ in requests}
    for rid, prompt, n in requests:
        sched.add_request(rid, prompt, SamplingParams(temperature=0.0), StopConditions(max_tokens=n, ignore_eos=True))
    for _ in range(400):
        if not sched.has_work():
            break
        for seq, o in sched.step():
            if o.token_id >= 0:
                out[seq.request_id].append(o.token_id)
    assert not sched.has_work()
    return out


REQUESTS = [("a", list(range(3, 23)), 9), ("b", list(range(40, 47)), 12), ("c", [5] * 70, 6)]


def warnings_of_the_store(caplog):
    return [r for r in caplog.records if r.name == program_store.logger.name]


def files(cache_dir):
    return sorted(os.path.join(d, f) for d, _, fs in os.walk(os.path.join(cache_dir, "programs")) for f in fs)


def by_key(keyed):
    return {(e.kind, e.key, e.fun_name): e for e in keyed}


# --- a second set-up takes every keyed program from the store -------------------------------------------------------------


@pytest.mark.parametrize("model", MODELS)
def test_a_second_build_takes_every_keyed_program_from_the_store(cache_on, model):
    _, cold, first = build(model)
    assert first["store_misses"] == first["keyed"] == len(cold) > 10 and first["store_hits"] == 0
    stored = files(cache_on)
    assert len(stored) == first["store_misses"] and all(f.endswith(SUFFIX) for f in stored)  # one file a key, no temporary left
    assert sum(map(os.path.getsize, stored)) < 16 << 20
    # A first run compiles nothing twice: the calibrated decode program is
    # loaded, not compiled again, though its key's export is a second file.
    assert sum(e.cache == "miss" for e in cold) == len(cold) - sum(e.kind == "calibrate" for e in cold)

    _, warm, second = build(model)
    assert second["store_hits"] == first["store_misses"] == second["keyed"] and second["store_misses"] == 0
    assert files(cache_on) == stored
    assert all(e.cache == "hit" for e in warm)  # one HLO a key, cold or warm: the persistent cache holds it
    assert second["executables"] == first["executables"] and second["eager"] == first["eager"]
    assert [(e.kind, e.key, e.fun_name, e.phase, e.in_one_chunk) for e in warm] == [
        (e.kind, e.key, e.fun_name, e.phase, e.in_one_chunk) for e in cold]
    # A hit traces one primitive and lowers a module it only embeds.
    assert all(e.nested_traces == 0 for e in warm)
    # Seconds, summed over the programs that hold a model's forward pass: 0.10-0.16 of a miss's on an idle machine at
    # these sizes (0.19 on the chip at evabyte's, PERF.md section 6, PR 48); a third leaves room for five busy workers.
    hit, miss = by_key(warm), by_key(cold)
    steps = [k for k in hit if k[0] in STEP_KINDS and not (k[0] == "decode" and ("calibrate", k[1], k[2]) in miss)]
    assert sum(hit[k].trace_s + hit[k].lower_s for k in steps) < sum(miss[k].trace_s + miss[k].lower_s for k in steps) / 3


@pytest.mark.parametrize("model", MODELS)
def test_served_tokens_equal_a_storeless_engines(cache_on, model):
    """Programs stored by an engine of one seed serve another seed's weights
    as that seed's own tracing would: nothing of the weights is in a module.
    A key first met in serving goes through the same lookup."""
    build(model, seed=0)
    engine, _, summary = build(model, seed=7)
    assert summary["store_misses"] == 0 and summary["store_hits"] == summary["keyed"]
    total0 = BUILD_LOG.total
    got = serve(engine.scheduler, REQUESTS)  # (a prompt of 70 tokens takes a table of 6 blocks, which warm-up at 64 tokens never met)
    in_serving = list(BUILD_LOG.entries)[total0 - BUILD_LOG.total:] if BUILD_LOG.total > total0 else []
    assert in_serving and all(e.phase == "serving" and e.store == "miss" for e in in_serving if e.kind != "eager")

    jax.config.update("jax_enable_compilation_cache", False)
    plain, _, _ = build(model, seed=7, warmup_ctx=0)  # (no warm-up: it builds what it serves)
    assert plain.scheduler._store is None
    assert serve(plain.scheduler, REQUESTS) == got
    assert all(len(got[rid]) == n for rid, _, n in REQUESTS)

    jax.config.update("jax_enable_compilation_cache", True)
    again, _, _ = build(model, seed=7)
    total0 = BUILD_LOG.total
    assert serve(again.scheduler, REQUESTS) == got
    stored = [e for e in list(BUILD_LOG.entries)[total0 - BUILD_LOG.total:] if e.kind != "eager"]
    assert stored and all(e.store == "hit" and e.cache == "hit" for e in stored)


# --- what invalidates, what falls back ----------------------------------------------------------------------------------


def test_one_changed_byte_of_the_source_misses_every_key(cache_on, monkeypatch):
    _, _, first = build("tiny")
    stored = files(cache_on)
    monkeypatch.setattr(program_store, "source_digest", lambda: "0" * 64)
    _, _, second = build("tiny")
    assert second["store_hits"] == 0 and second["store_misses"] == first["store_misses"]
    after = files(cache_on)
    assert len(after) == 2 * len(stored) and set(stored) < set(after)
    assert len(os.listdir(os.path.join(cache_on, "programs"))) == 2  # a generation each


def test_the_digest_holds_every_byte_of_every_source_file(tmp_path, monkeypatch):
    package = tmp_path / "pkg"
    (package / "engine" / "models").mkdir(parents=True)
    (package / "engine" / "models" / "m.py").write_text("x = 1\n")
    (package / "engine" / "store.py").write_text("")
    (package / "notes.txt").write_text("not source")
    monkeypatch.setattr(program_store, "__file__", str(package / "engine" / "store.py"))

    def digest():
        program_store.source_digest.cache_clear()
        try:
            return program_store.source_digest()
        finally:
            program_store.source_digest.cache_clear()

    d0 = digest()
    (package / "notes.txt").write_text("still not source")
    assert digest() == d0
    (package / "engine" / "models" / "m.py").write_text("x = 2\n")
    d1 = digest()
    assert d1 != d0
    (package / "engine" / "models" / "m.py").rename(package / "engine" / "models" / "n.py")
    assert digest() not in (d0, d1)


def test_a_truncated_or_garbage_file_is_a_miss_that_is_rewritten(cache_on, caplog):
    build("tiny")
    stored = files(cache_on)
    whole = {f: open(f, "rb").read() for f in stored}
    mixed = next(f for f in stored if "mixed-" in f)
    sampler = next(f for f in stored if "sampler-" in f)
    prefill = next(f for f in stored if "prefill-" in f)
    with open(mixed, "wb") as f:
        f.write(whole[mixed][: len(whole[mixed]) // 2])  # truncated
    with open(sampler, "wb") as f:
        f.write(os.urandom(4096))  # garbage
    with open(prefill, "wb") as f:
        f.write(whole[prefill][:-1] + bytes([whole[prefill][-1] ^ 1]))  # one flipped bit of the payload
    with caplog.at_level(logging.WARNING, logger=program_store.logger.name):
        engine, keyed, summary = build("tiny")
    assert (summary["store_misses"], summary["store_hits"]) == (3, summary["keyed"] - 3) and not warnings_of_the_store(caplog)
    # Rewritten whole (not byte for byte: a module carries the line numbers of the call stack it was traced under).
    assert files(cache_on) == stored and all(engine.scheduler._store.read(f) is not None for f in stored)
    assert build("tiny")[2]["store_misses"] == 0
    assert len(serve(engine.scheduler, REQUESTS[:1])["a"]) == 9


def test_a_mesh_of_two_devices_takes_todays_path(cache_on):
    engine, keyed, summary = build("tiny", parallel=ParallelConfig(tp=2))
    assert engine.scheduler.mesh.size == 2 and engine.scheduler._store is None
    assert summary["store_hits"] == summary["store_misses"] == 0 and all(e.store is None for e in keyed)
    assert not isinstance(engine.scheduler._decode_jit, StoredJit) and files(cache_on) == []


def test_with_the_cache_off_no_store_is_opened_and_no_file_written(cache_on):
    jax.config.update("jax_enable_compilation_cache", False)
    assert program_store_dir() is None
    engine, _, _ = build("tiny", warmup_ctx=0)
    assert engine.scheduler._store is None and not isinstance(engine.scheduler._decode_jit, StoredJit)
    assert len(serve(engine.scheduler, REQUESTS[:1])["a"]) == 9
    summary = BUILD_LOG.summary(engine.scheduler.flight.since_ns)
    assert summary["keyed"] > 0 and summary["store_hits"] == summary["store_misses"] == 0
    assert all(e["store"] is None for e in summary["costliest"]) and os.listdir(cache_on) == []


def test_an_export_that_raises_takes_todays_path_for_its_key(cache_on, monkeypatch, caplog):
    real = jax.export.export

    def refuses_windows(fun_jit, **kw):
        if fun_jit.__name__.startswith("decode_multi"):
            raise NotImplementedError("no export of windows today")
        return real(fun_jit, **kw)

    monkeypatch.setattr(jax.export, "export", refuses_windows)
    with caplog.at_level(logging.WARNING, logger=program_store.logger.name):
        engine, keyed, summary = build("tiny")
    windows = [e for e in keyed if e.kind == "decode_multi"]
    assert windows and all(e.store is None for e in windows) and all(e.store == "miss" for e in keyed if e.kind != "decode_multi")
    assert len(warnings_of_the_store(caplog)) == len(windows) and "no export of windows today" in caplog.text
    assert not any("decode_multi" in f for f in files(cache_on))
    assert len(serve(engine.scheduler, REQUESTS[:1])["a"]) == 9


# --- the files ----------------------------------------------------------------------------------------------------------


def test_a_file_is_read_only_as_it_was_written(tmp_path):
    store = ProgramStore(str(tmp_path), "context")
    exported = jax.export.export(jax.jit(lambda x: x + 1))(jax.ShapeDtypeStruct((4,), "float32"))
    payload = bytes(exported.serialize())
    path = store.path("kind", "name", "d" * 64)
    assert store.read(path) is None  # absent
    store.write(path, payload)
    assert os.listdir(store.dir) == [os.path.basename(path)] and path.endswith(SUFFIX)  # no temporary file is left
    assert store.read(path).in_avals == exported.in_avals
    blob = open(path, "rb").read()
    assert blob.startswith(MAGIC) and blob.endswith(payload)
    for bad in (b"", blob[:10], blob[:-1], b"x" + blob[1:], blob + b"\0", payload):
        with open(path, "wb") as f:
            f.write(bad)
        assert store.read(path) is None


def test_a_static_argument_names_its_program_and_the_stored_module_is_called_without_it(tmp_path):
    """As ``prefill_mm``: a static argument between traced ones, a donated one before it."""
    import jax.numpy as jnp

    def step(pool, x, flag, y):
        return pool.at[0].set(x[0]), (x + y if flag else x - y)

    x, y = jnp.arange(4.0), jnp.ones(4)
    for turn in range(2):  # the second turn's object finds both programs on disk
        stored = StoredJit(ProgramStore(str(tmp_path), "context"), step, donate_argnums=(0,), static_argnums=(2,), closure=("widths", 16))
        assert isinstance(stored, StoredJit) and stored.__name__ == "step"
        pool = jnp.zeros(8)
        pool, added = stored(pool, x, True, y)
        pool, taken = stored(pool, x, False, y)
        assert added.tolist() == [1, 2, 3, 4] and taken.tolist() == [-1, 0, 1, 2] and pool[0] == 0
        assert len(stored._programs) == 2 and stored(pool, x, True, y)[1].tolist() == [1, 2, 3, 4] and len(stored._programs) == 2
        assert len(stored(jnp.zeros(16), jnp.arange(2.0), True, jnp.ones(2))[1]) == 2 and len(stored._programs) == 3  # another shape, another program
        assert "step" in stored.lower(pool, x, True, y).as_text() and len(stored._programs) == 3  # (a lowering is not kept)
        assert sorted(os.listdir(ProgramStore(str(tmp_path), "context").dir)) == sorted(os.listdir(stored.store.dir)) and len(os.listdir(stored.store.dir)) == 3
    other = StoredJit(ProgramStore(str(tmp_path), "context"), step, donate_argnums=(0,), static_argnums=(2,), closure=("widths", 32))
    other(jnp.zeros(8), x, True, y)
    assert len(os.listdir(other.store.dir)) == 4  # what a function closes over names its programs too


def test_generations_older_than_the_newest_few_are_dropped_when_a_new_one_is_written(tmp_path):
    root = tmp_path / "programs"
    for i in range(GENERATIONS + 3):
        old = root / f"{i:016x}"
        old.mkdir(parents=True)
        (old / f"decode-decode-0{SUFFIX}").write_bytes(b"old")
        os.utime(old, (1_000_000 + i, 1_000_000 + i))
    store = ProgramStore(str(root), "context", source="a changed source")
    assert not os.path.isdir(store.dir)  # nothing is made until something is written
    store.write(store.path("decode", "decode", "1" * 64), b"payload")
    kept = sorted(os.listdir(root))
    assert kept == sorted([os.path.basename(store.dir)] + [f"{i:016x}" for i in range(3, GENERATIONS + 3)])
    store.write(store.path("decode", "decode", "2" * 64), b"payload")  # (a second write drops nothing more)
    assert sorted(os.listdir(root)) == kept and len(os.listdir(store.dir)) == 2


def test_the_generation_names_the_source_the_versions_and_the_device(tmp_path, monkeypatch):
    a = ProgramStore(str(tmp_path), "context")
    assert a.generation == ProgramStore(str(tmp_path), "another context").generation and a.dir.startswith(str(tmp_path))
    assert ProgramStore(str(tmp_path), "context", source="changed").generation != a.generation
    monkeypatch.setattr(jax, "__version__", "0.0.0")
    assert ProgramStore(str(tmp_path), "context").generation != a.generation
