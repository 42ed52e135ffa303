"""What a dispatch of the served path costs the host (PR 35): one upload of one
packed buffer (two when a decode table changed), one program that runs the step
and takes every row's argmax, one blocking read-back.

(a) the program's tokens are the host path's for greedy rows, through
``_mixed_step`` (a chunk that ends its prompt and chunks that do not),
``_decode_step``, ``_prefill_one`` and ``_decode_multi``, whose window also
draws in the program, from the key the host folded; (b) one row that needs the
host between its logits and its token (it draws, or wants logprobs, penalties,
...) takes its dispatch to the host path, which yields what it yielded before;
(c) uploads, programs and read-backs of every dispatch, counted from outside;
(d) the keys ``Scheduler.warmup`` registers and the executables it builds at
the four cells' scheduler settings, as literal counts; (e) after
``Scheduler.warmup`` a stream that meets a roll, an admission, a finished
prompt and a host-path row builds nothing."""

import collections
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import get_module
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions, fold_key, pack_operands

PRESETS = ["tiny", "tiny-moe", "tiny-eva", "tiny-hybrid"]
# A quarter of the presets' logit spread (random weights): a row's best token then has 0.15 to 0.9 of the probability,
# so draws differ from key to key without being uniform noise.
TEMPERATURE = {"tiny": 0.04, "tiny-moe": 0.04, "tiny-eva": 0.04, "tiny-hybrid": 0.01}


def sampled(preset, **more):
    return dict(temperature=TEMPERATURE[preset], top_k=20, top_p=0.9, **more)


# Rows that need the host between their logits and their token (Scheduler._needs_host), by kind.
HOST_ROWS = {
    "sampled": lambda preset: sampled(preset),
    "seeded": lambda preset: sampled(preset, seed=7),
    "logprobs": lambda preset: dict(logprobs=True),
    "top_logprobs": lambda preset: dict(top_logprobs=3),
    "penalties": lambda preset: dict(frequency_penalty=0.7, presence_penalty=0.3),
}


def params_of(cfg):
    return get_module(cfg).init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


@pytest.fixture(scope="module")
def weights():
    return {preset: params_of(get_config(preset)) for preset in PRESETS}


def mk_sched(preset, weights, cfg=None, rng_seed=11, **kw) -> Scheduler:
    sc = dict(num_blocks=96, max_running=4, prefill_buckets=[16], decode_buckets=[4], max_prefill_chunk=16,
              mixed_prefill_budget=16, num_scheduler_steps=1, enable_prefix_caching=False)
    sc.update(kw)
    return Scheduler(cfg or get_config(preset), weights[preset], SchedulerConfig(**sc), dtype=jnp.float32, rng_seed=rng_seed)


def add(sched, rid, prompt, max_tokens, sampling):
    sched.add_request(rid, prompt, SamplingParams(**{"temperature": 0.0, **sampling}),
                      StopConditions(max_tokens=max_tokens, ignore_eos=True))


def serve(sched, arrivals):
    """``arrivals`` {iteration: [request]} (one at a time, so that none is
    admitted in a wave and the later ones meet running rows: mixed steps), to
    the end: {id: [(token, logprob, top_logprobs)]}."""
    out = collections.defaultdict(list)
    for i in range(600):
        for r in arrivals.get(i, ()):
            add(sched, *r)
        if i > max(arrivals) and not sched.has_work():
            break
        for seq, o in sched.step():
            if o.token_id >= 0:
                out[seq.request_id].append((o.token_id, o.logprob, o.top_logprobs))
    assert not sched.has_work()
    return dict(out)


def entries(sched, after_step=0):
    """The ``sched.step`` entries that dispatched, oldest first."""
    return [e[4] for e in sched.flight.log.spans if e[0] == "sched.step" and e[3] > after_step and e[4] and "kind" in e[4]]


def traffic(c_sampling=None):
    """Two rows decode; a prompt of 40 joins them: chunks of 16, 16 and 8 ride
    mixed steps, two that do not end the prompt and the one that does."""
    return {0: [("a", list(range(1, 13)), 14, {})], 1: [("b", list(range(20, 29)), 14, {})],
            3: [("c", list(range(40, 80)), 6, c_sampling or {})]}


def on_host_path(monkeypatch):
    """Every row takes the host path: the parent's way to a token."""
    monkeypatch.setattr(Scheduler, "_needs_host", classmethod(lambda cls, seq: True))
    monkeypatch.setattr(Scheduler, "_host_between_tokens", staticmethod(lambda seq: True))


# --- (a) the program's tokens are the host path's --------------------------------------------------------------


@pytest.mark.parametrize("steps", [1, 8], ids=["single-steps", "windows"])
@pytest.mark.parametrize("preset", PRESETS)
def test_greedy_rows_get_from_the_program_what_the_host_path_gives_them(preset, steps, weights, monkeypatch):
    program = mk_sched(preset, weights, num_scheduler_steps=steps)
    got = serve(program, traffic())
    kinds = collections.Counter(e["kind"] for e in entries(program))
    # a's prompt; b's and c's chunks of 16, 16, 8 beside running rows; then single steps or windows.
    assert kinds["prefill"] == 1 and kinds["mixed"] == 4 and kinds["decode" if steps == 1 else "decode_multi"] >= 2, kinds
    assert {e["sampled"] for e in entries(program)} == {"program"}
    assert program.debug_state()["sampled_in_program_total"] == sum(kinds.values())
    on_host_path(monkeypatch)
    host = mk_sched(preset, weights, num_scheduler_steps=steps)
    assert serve(host, traffic()) == got
    assert {e["sampled"] for e in entries(host)} == {"host"} and "decode_multi" not in {e["kind"] for e in entries(host)}
    # Both count steps alike: a row that draws later finds the key it would have found.
    assert steps == 8 or host._step_counter == program._step_counter


@pytest.mark.parametrize("how", ["greedy", "sampled"])
@pytest.mark.parametrize("preset", PRESETS)
def test_step_programs_give_the_models_logits_their_argmax_and_the_windows_draws(preset, how, weights):
    """The jitted wrappers against the model's own step functions: a window's
    tokens are ``decode_multi``'s with the key ``jax.random.fold_in(rng,
    counter)`` (what the parent folded on the device, the host folds now); a
    single step returns the model's logits and their argmax."""
    cfg, p = get_config(preset), weights[preset]
    sampling = sampled(preset) if how == "sampled" else dict(temperature=0.0, top_k=0, top_p=1.0)
    sched = mk_sched(preset, weights, num_scheduler_steps=8)
    model, B, W, S, counter = sched._model, 4, 4, 16, 41
    te = np.full((B,), sampling["temperature"], np.float32)
    tk = np.full((B,), sampling["top_k"], np.int32)
    tp = np.full((B,), sampling["top_p"], np.float32)
    rows = np.zeros((6, B), np.int32)
    rows[0], rows[1], rows[2] = [5, 6, 7, 8], [3, 4, 5, 6], 1
    rows[3], rows[4], rows[5] = te.view(np.int32), tk, tp.view(np.int32)
    tables = np.arange(1, 1 + B * W, dtype=np.int32).reshape(B, W)
    act = jnp.ones((B,), bool)
    slots = sched.slots.num_slots if sched.slots else 0
    aux = 1 if cfg.is_hybrid else 0  # layer_types: the expert layer's counts ride every result

    def fresh():
        c = KvCacheArrays.create(cfg, 24, dtype=jnp.float32, num_slots=slots)
        if cfg.is_hybrid:  # each table's first block names a slot
            c.k, c.v = sched._open_slot_jit(c.k, c.v, jnp.int32(1), jnp.int32(1))
        return c

    # decode_multi: the window's tokens.
    c = fresh()
    want = model.decode_multi(p, cfg, c.k, c.v, jnp.asarray(rows[0]), jnp.asarray(rows[1]), jnp.asarray(tables), act,
                              jnp.asarray(te), jnp.asarray(tk), jnp.asarray(tp), jax.random.fold_in(sched._rng, counter), 8)
    c = fresh()
    buf = pack_operands(rows, fold_key(sched._rng_words, counter))
    got = sched._decode_multi_jits[8](p, c.k, c.v, jnp.asarray(buf), jnp.asarray(tables))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    if how == "sampled":
        return  # a single step that holds a row which draws is the host path's: (b)
    # decode: tokens and logits.
    c = fresh()
    want = model.decode(p, cfg, c.k, c.v, jnp.asarray(rows[0]), jnp.asarray(rows[1]), jnp.asarray(tables), act)
    c = fresh()
    got = sched._decode_jit(p, c.k, c.v, jnp.asarray(pack_operands(rows[:3])), jnp.asarray(tables))
    assert len(got) == len(want) + 1
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[0]), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1]).argmax(-1))
    # mixed_step: [the chunk's last row ; the decode rows].
    chunk, ptab = np.arange(30, 30 + S, dtype=np.int32), np.zeros((16,), np.int32)
    ptab[:2] = [20, 21]
    c = fresh()
    want = model.mixed_step(p, cfg, c.k, c.v, jnp.asarray(chunk), jnp.int32(S - 3), jnp.int32(0), jnp.asarray(ptab),
                            jnp.asarray(rows[0]), jnp.asarray(rows[1]), jnp.asarray(tables), act)
    c = fresh()
    buf = pack_operands(chunk, S - 3, 0, rows[:3], ptab)
    got = sched._get_mixed_jit((S, 16, B, W))(p, c.k, c.v, jnp.asarray(buf), jnp.asarray(tables))
    assert len(got) == len(want) + 2 and len(want) == 3 + aux
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[0][:1]), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[0][1:]), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.concatenate([np.asarray(got[1]), np.asarray(got[2])]).argmax(-1))
    # prefill: the prompt's first token.
    c = fresh()
    want = model.prefill(p, cfg, c.k, c.v, jnp.asarray(chunk), jnp.int32(S - 3), jnp.int32(0), jnp.asarray(ptab))
    c = fresh()
    got = sched._prefill_jit(p, c.k, c.v, jnp.asarray(pack_operands(chunk, S - 3, 0)), jnp.asarray(ptab))
    np.testing.assert_allclose(np.asarray(got[1][0]), np.asarray(want[0]), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1]).argmax(-1))


def test_the_seed_is_data_so_two_seeds_build_one_program(weights):
    """A key closed over would be a constant of the HLO and every ``--seed``
    would miss the compile cache: the lowered programs of two engine seeds
    are the same text (a window's key rides its packed operands)."""
    texts = []
    for seed in (3, 4_000_000_007 & 0x7FFFFFFF):
        s = mk_sched("tiny", weights, rng_seed=seed, num_scheduler_steps=8)
        k, v, p, i32 = s.cache.k, s.cache.v, s.params, jnp.int32
        tables = jnp.zeros((4, 4), i32)
        texts.append([
            s._decode_jit.lower(p, k, v, jnp.zeros((3 * 4,), i32), tables).as_text(),
            s._decode_multi_jits[8].lower(p, k, v, jnp.zeros((6 * 4 + 2,), i32), tables).as_text(),
            s._prefill_jit.lower(p, k, v, jnp.zeros((16 + 2,), i32), jnp.zeros((16,), i32)).as_text(),
            s._get_mixed_jit((16, 16, 4, 4)).lower(p, k, v, jnp.zeros((16 + 2 + 12 + 16,), i32), tables).as_text(),
        ])
    assert texts[0] == texts[1]


@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 - 1, 123456789])
def test_the_host_folds_the_keys_jax_folds(seed):
    key = jax.random.PRNGKey(seed)
    words = tuple(int(w) for w in np.asarray(key))
    for data in (0, 1, 2, 41, 2**31 - 1, 987654321):
        assert fold_key(words, data) == tuple(int(w) for w in np.asarray(jax.random.fold_in(key, data)))


@pytest.mark.parametrize("seed", [None, 0, 7, 2**31 - 1, -5])
def test_the_host_paths_key_is_the_one_jax_folds(seed, weights):
    s = mk_sched("tiny", weights)
    s._step_counter = 123
    seq = type("Seq", (), {"sampling": SamplingParams(seed=seed), "output_ids": [1, 2, 3]})()
    want = (jax.random.fold_in(s._rng, 123) if seed is None else jax.random.fold_in(jax.random.PRNGKey(seed), 3))
    np.testing.assert_array_equal(np.asarray(s._key(seq)), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(s._key()), np.asarray(jax.random.fold_in(s._rng, 123)))
    assert s._key().dtype == s._rng.dtype and s._key().shape == s._rng.shape  # the samplers' warmed signature


# --- (b) one row that needs the host takes its dispatch there -----------------------------------------------------


@pytest.mark.parametrize("row", list(HOST_ROWS))
@pytest.mark.parametrize("preset", PRESETS)
def test_a_row_that_needs_the_host_takes_its_dispatch_to_the_host_path(preset, row, weights, monkeypatch):
    """The late request ``c`` is such a row: the dispatch that ends its prompt
    and every dispatch it then rides are the host's, the others the program's;
    every row's tokens (and ``c``'s logprobs) are what the host path alone yields."""
    mixed = mk_sched(preset, weights)
    got = serve(mixed, traffic(HOST_ROWS[row](preset)))
    how = [e["sampled"] for e in entries(mixed)]
    # The chunk that ends c's prompt and the five steps c then rides are the host's: nothing before, nothing after.
    assert how.count("host") == 6 and how[0] == how[-1] == "program", how
    assert how[how.index("host") : how.index("host") + 6] == ["host"] * 6, how
    assert [e["kind"] for e in entries(mixed)][how.index("host")] == "mixed"
    if "logprobs" in row:
        assert all(lp is not None and lp <= 0.0 for _, lp, _ in got["c"]) and all(lp is None for _, lp, _ in got["a"] + got["b"])
    if row == "top_logprobs":
        assert all(len(tlp) == 3 for _, _, tlp in got["c"])
    if row in ("sampled", "seeded"):
        # The draws are the engine seed's, or the request's own: another engine seed moves the first alone.
        other = serve(mk_sched(preset, weights, rng_seed=12), traffic(HOST_ROWS[row](preset)))
        assert (other["a"], other["b"]) == (got["a"], got["b"]) and (other["c"] == got["c"]) == (row == "seeded")
    on_host_path(monkeypatch)
    assert serve(mk_sched(preset, weights), traffic(HOST_ROWS[row](preset))) == got


@pytest.mark.parametrize("preset", PRESETS)
def test_a_window_draws_in_its_program_for_the_rows_that_draw(preset, weights):
    """``decode_multi`` holds the sampler, as before: rows that draw ride
    windows (their single steps and their chunks are the host path's), each
    window on the key the host folded for its step."""
    arrivals = {0: [("a", list(range(1, 13)), 20, sampled(preset))], 1: [("b", list(range(20, 29)), 20, {})]}
    sched = mk_sched(preset, weights, num_scheduler_steps=8)
    got = serve(sched, arrivals)
    how = collections.Counter((e["kind"], e["sampled"]) for e in entries(sched))
    assert how[("decode_multi", "program")] >= 2 and how[("mixed", "host")] == 1 and ("decode_multi", "host") not in how, how
    other = serve(mk_sched(preset, weights, num_scheduler_steps=8, rng_seed=12), arrivals)
    assert other["b"] == got["b"] and other["a"] != got["a"]


# --- (c), (d), (e): the four cells' scheduler settings at tiny widths -----------------------------------------------

# benchmark/configs/<cell>.json "scheduler" with the chunk and batch buckets cut to the presets' sizes: one chunk
# bucket, two batch buckets, windows of 8, tables up to 16 blocks (evabyte: up to 20; "paged" with flash chunks as
# on the chip, where since PR 52 its chunks walk tiles and has_prefix keys nothing).
CELLS = {
    "tiny": (lambda: get_config("tiny"), dict(itl_budget_ms=5000.0, enable_prefix_caching=True), 256),  # (tiny-moe: the same keys)
    "tiny-eva": (lambda: get_config("tiny-eva").replace(max_seq_len=544, attention_impl="paged", prefill_impl="flash"), dict(), 544),
    "tiny-hybrid": (lambda: get_config("tiny-hybrid"), dict(), 128),
}
# kind -> keys Scheduler.warmup registers; and the executables behind them (the parent's: PERF.md section 6, PR 35).
WARMED = {
    "tiny": dict(keys={"decode": 10, "decode_multi": 10, "mixed": 10, "prefill": 1, "admit": 2, "kv_block_copy": 1},
                 executables={"decode": 10, "decode_multi": 10, "mixed": 10, "prefill": 1}),
    "tiny-eva": dict(keys={"decode": 12, "decode_multi": 12, "mixed": 24, "prefill": 2, "eva_roll": 1},
                     executables={"decode": 12, "decode_multi": 12, "mixed": 24, "prefill": 2}),
    "tiny-hybrid": dict(keys={"decode": 10, "decode_multi": 10, "mixed": 10, "prefill": 1, "open_slot": 1},
                        executables={"decode": 10, "decode_multi": 10, "mixed": 10, "prefill": 1}),
}


# warmup()'s count beside the four step-program kinds: 4 a batch bucket (three samplers, the row keys), 2 first-token
# logprobs, 1 one-row sampler a chunk bucket; then waves (2) and the block copy (prefix caching), the roll, the slot.
WARMED_REST = {"tiny": 8 + 2 + 1 + 2 + 1, "tiny-eva": 8 + 2 + 1 + 1, "tiny-hybrid": 8 + 2 + 1 + 1}


class Builds:
    """What JAX really builds (chip_smoke.py::CompileMeter's event)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


BUILDS = Builds()


@pytest.fixture(scope="module", params=list(CELLS))
def warmed(request, weights):
    """(preset, warmed scheduler, what warmup returned). Parametrised at module
    scope, so that a preset's three tests run together and its executables are
    gone before the next preset builds its own (a process that holds four
    warmed schedulers' programs at once runs out of mappings)."""
    preset = request.param
    cfg_of, extra, ctx = CELLS[preset]
    s = mk_sched(preset, weights, cfg=cfg_of(), max_running=8, prefill_buckets=[32], max_prefill_chunk=32,
                 mixed_prefill_budget=32, decode_buckets=[4, 8], num_scheduler_steps=8, num_blocks=160,
                 **{"enable_prefix_caching": False, **extra})
    count = s.warmup(ctx_tokens=ctx)
    s.flight.mark_warmup_done(warmed=True)
    yield preset, s, count
    del s
    gc.collect()


def test_warmup_registers_the_parents_keys_and_builds_one_executable_a_key(warmed):
    preset, sched, count = warmed
    want = WARMED[preset]
    keys = collections.Counter(k[0] for k in sched.flight._exec_keys)
    assert dict(keys) == want["keys"]
    static = 2 if sched._hp_static else 1  # has_prefix static: a prompt's first chunk and its later ones are two executables
    assert not sched._hp_static  # no cell's chunk takes the flash path's own attention (llama.chunk_walks_tiles)
    built = {
        "decode": sched._decode_jit._cache_size(),
        "decode_multi": sum(f._cache_size() for f in sched._decode_multi_jits.values()),
        "mixed": sum(f._cache_size() for f in sched._mixed_jits.values()),
        "prefill": sched._prefill_jit._cache_size(),
    }
    assert built == want["executables"] and len(sched._mixed_jits) * static == built["mixed"]
    assert list(sched._decode_multi_jits) == [8]
    # (The samplers and the host path's helpers are jits of module functions, whose caches every Scheduler of the
    # process shares: warmup's own count stands for them.)
    assert count == sum(built.values()) + WARMED_REST[preset]


class Counted:
    """Counts the calls of the scheduler's jitted callables, the transfers up
    (``jax.device_put``) and the read-backs (``jax.device_get``)."""

    def __init__(self, sched, monkeypatch):
        self.programs = self.uploads = self.reads = 0
        for name in ("_decode_jit", "_prefill_jit", "_roll_jit", "_open_slot_jit", "_sample_jit", "_sample_lp_jit",
                     "_sample_tlp_jit", "_lp_jit", "_tlp_jit", "_kv_copy_jit"):
            if hasattr(sched, name):
                monkeypatch.setattr(sched, name, self._program(getattr(sched, name)))
        monkeypatch.setattr(sched, "_decode_multi_jits", {w: self._program(f) for w, f in sched._decode_multi_jits.items()})
        monkeypatch.setattr(sched, "_mixed_jits", {k: self._program(f) for k, f in sched._mixed_jits.items()})
        put, get = jax.device_put, jax.device_get

        def device_put(*a, **kw):
            self.uploads += 1
            return put(*a, **kw)

        def device_get(x):
            self.reads += 1
            return get(x)

        monkeypatch.setattr(jax, "device_put", device_put)
        monkeypatch.setattr(jax, "device_get", device_get)

    def _program(self, fn):
        def call(*a, **kw):
            self.programs += 1
            return fn(*a, **kw)

        return call

    def take(self):
        out = (self.uploads, self.programs, self.reads)
        self.programs = self.uploads = self.reads = 0
        return out


def stream(d_sampling=None):
    """Requests that meet, on every preset: a bare prefill, admissions beside
    running rows, prompts finished by mixed steps, windows and, for eva, rolls
    (a window is 32 positions) in prefill and in decode."""
    return {0: [("a", list(range(1, 31)), 24, {})], 1: [("b", list(range(50, 59)), 30, {})],
            3: [("c", list(range(60, 130)), 12, {})], 4: [("d", list(range(140, 150)), 9, d_sampling or {})]}


def test_a_dispatch_is_one_upload_one_program_one_read_back(warmed, monkeypatch):
    preset, sched, _ = warmed
    counted = Counted(sched, monkeypatch)
    arrivals = stream()
    step0, seen = sched.flight.log.step, []
    for i in range(400):
        for r in arrivals.get(i, ()):
            add(sched, *r)
        if i > max(arrivals) and not sched.has_work():
            break
        builds0 = BUILDS.n
        with jax.transfer_guard_host_to_device("disallow"):  # nothing goes up but through jax.device_put
            sched.step()
        assert BUILDS.n == builds0
        seen.append(counted.take())
    assert not sched.has_work()
    logged = [e for e in sched.flight.log.spans if e[0] == "sched.step" and e[3] > step0]
    assert len(logged) == len(seen)
    kinds = collections.Counter()
    for (_, _, _, _, attrs), (uploads, programs, reads) in zip(logged, seen):
        attrs = attrs or {}
        if "kind" not in attrs:
            assert (uploads, programs, reads) == (0, 0, 0)
            continue
        kinds[attrs["kind"]] += 1
        assert attrs["sampled"] == "program" and attrs["uploads"] == uploads
        # A roll and a slot taken are programs of their own, each with two scalars or a table going up.
        beside = attrs.get("rolls", 0) + (programs - 1 - attrs.get("rolls", 0) if preset == "tiny-hybrid" else 0)
        assert programs == 1 + beside and uploads in (1 + 2 * beside, 2 + 2 * beside), (attrs, uploads, programs)
        if attrs["kind"] == "prefill":
            assert uploads == 2 + 2 * beside  # the chunk's table goes up beside the packed operands
            assert reads <= 1  # a chunk that does not end its prompt reads nothing back
        else:
            assert reads == 1
    assert kinds["mixed"] >= 3 and kinds["decode_multi"] >= 3 and kinds["prefill"] >= 1, kinds
    if preset == "tiny-eva":
        assert sched.eva_rolls_total >= 3
    if preset == "tiny-hybrid":
        assert sched.slots.in_use == 0 and sched.kv_gauges()["ssm_slot_allocs_total"] >= 4
    # Where no decode table changed nothing but the packed operands goes up. (eva's blocks here hold 8 rows: every
    # window of 8 steps grows a table.)
    assert preset == "tiny-eva" or min(a["uploads"] for _, _, _, _, a in logged if a and a.get("kind") in ("mixed", "decode_multi")) == 1


def test_after_warmup_a_stream_builds_nothing(warmed):
    """A roll (eva), an admission (a slot taken, for layer_types), a prompt
    finished by a mixed step and a host-path row (logprobs: its chunk's first
    token and every step it rides are the host's): no key that warmup did not
    register, and nothing built at all."""
    preset, sched, _ = warmed
    compiles0, builds0, host0 = sched.flight.compiles_after_warmup_total, BUILDS.n, sched.sampled_on_host_total
    out = serve(sched, stream(dict(logprobs=True)))
    assert {r: len(t) for r, t in out.items()} == {"a": 24, "b": 30, "c": 12, "d": 9}
    assert all(lp is not None for _, lp, _ in out["d"])
    assert sched.sampled_on_host_total - host0 >= 9
    assert sched.flight.compiles_after_warmup_total == compiles0, sched.flight.post_warmup_keys
    assert BUILDS.n == builds0
