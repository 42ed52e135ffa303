"""The selection table of ``Scheduler._decode_step`` (README "Step programs"),
stated once: speculation if a draft is attached and the rows allow it, a
multi-step window (``decode_multi``) if every row is window-eligible and the
window can be reserved, else one single step (``decode``) for the whole batch.
Also what ``Scheduler.warmup`` registers: exactly the kinds that table marks."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions
from dynamo_tpu.llm.tokenizer import ByteTokenizer
from dynamo_tpu.logits_processing import AllowedTokensProcessor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
DECODE_STEP_KINDS = ("spec", "decode_multi", "decode")  # what _decode_step dispatches
GONE = {"decode_sample", "kv_rollback", "decode_fused", "decode_fused_sampled", "decode_fused_guided", "spec_fused"}


def mk_sched(cfg=CFG, params=PARAMS, **kw) -> Scheduler:
    sc = dict(num_blocks=128, max_running=8, prefill_buckets=[16, 32], decode_buckets=[1, 2, 4],
              num_scheduler_steps=8, enable_prefix_caching=False, guided_pool_rows=256)
    sc.update(kw)
    return Scheduler(cfg, params, SchedulerConfig(**sc), dtype=jnp.float32)


def serve(sched, requests):
    """Add ``requests`` ((id, prompt, max_tokens, sampling, guided)), run to the
    end: ({id: tokens}, {id: finish_reason})."""
    for rid, prompt, max_tokens, sampling, guided in requests:
        sched.add_request(rid, prompt, SamplingParams(**{"temperature": 0.0, **sampling}),
                          StopConditions(max_tokens=max_tokens, ignore_eos=True), guided=guided)
    toks, fin = {}, {}
    for _ in range(1000):
        if not sched.has_work():
            break
        for seq, o in sched.step():
            if o.token_id >= 0:
                toks.setdefault(seq.request_id, []).append(o.token_id)
            if o.finished:
                fin[seq.request_id] = o.finish_reason
    assert not sched.has_work() and not sched.waiting and not sched.running
    return toks, fin


def decode_step_kinds(sched, after_step=0):
    """Kinds of the ``sched.step`` entries that ``_decode_step`` dispatched."""
    return [e[4]["kind"] for e in sched.flight.log.spans
            if e[0] == "sched.step" and e[3] > after_step and e[4] and e[4]["kind"] in DECODE_STEP_KINDS]


def batch_of(sampling, guided=None, n=3, max_tokens=12):
    return [(f"r{i}", list(range(1 + i, 19 + i)), max_tokens, sampling, guided) for i in range(n)]


# row type: (sampling, guided spec, the program its batch rides, tokens are a function of the request alone)
ROWS = {
    "greedy": (dict(), None, "decode_multi", True),
    "sampled": (dict(temperature=0.8, top_k=20, top_p=0.9), None, "decode_multi", False),
    "seeded": (dict(temperature=0.8, top_k=20, seed=7), None, "decode", True),
    "guided": (dict(), {"kind": "regex", "pattern": "[a-e]{40}"}, "decode", True),
    "logprobs": (dict(logprobs=True), None, "decode", True),
    "top_logprobs": (dict(top_logprobs=3), None, "decode", True),
    "penalties": (dict(frequency_penalty=0.7, presence_penalty=0.3), None, "decode", True),
    "processor": (dict(logits_processors=[AllowedTokensProcessor(allowed=list(range(40, 90)))]), None, "decode", True),
}


@pytest.fixture(scope="module")
def warmed():
    """One warmed scheduler with a grammar pool, which every row type's batch is served by in turn."""
    sched = mk_sched(decode_buckets=[4], prefill_buckets=[32])
    sched.attach_guided(ByteTokenizer())
    sched.warmup(ctx_tokens=64)
    sched.flight.mark_warmup_done(warmed=True)
    return sched


@pytest.mark.parametrize("row", list(ROWS))
def test_batch_rides_the_program_its_rows_allow(row, warmed):
    sampling, guided, kind, deterministic = ROWS[row]
    step0, compiles0 = warmed.flight.log.step, warmed.flight.compiles_after_warmup_total
    toks, fin = serve(warmed, batch_of(sampling, guided))
    assert {r: len(t) for r, t in toks.items()} == {"r0": 12, "r1": 12, "r2": 12}
    assert set(fin.values()) == {"length"}
    kinds = decode_step_kinds(warmed, after_step=step0)
    assert kinds and set(kinds) == {kind}, kinds
    # No executable key that warmup did not register.
    assert warmed.flight.compiles_after_warmup_total == compiles0, warmed.flight.post_warmup_keys
    if deterministic:
        single = mk_sched(num_scheduler_steps=1)
        single.attach_guided(ByteTokenizer())
        assert toks == serve(single, batch_of(sampling, guided))[0]
    if guided:
        assert all(re.fullmatch("[a-e]{12}", ByteTokenizer().decode(t)) for t in toks.values())


def test_what_warmup_compiled_is_inside_the_static_enumeration(warmed):
    """The flight recorder's keys of a warmed scheduler against dtlint WARM001's reading of
    ``Scheduler.warmup()``: every compiled kind is one the linter lists, at an arity it lists."""
    from tools.dtlint.rules_warmup import static_warmup_report

    static = static_warmup_report(REPO)["warmed"]
    compiled = warmed.flight.exec_key_summary()
    assert {"prefill", "decode", "decode_multi"} <= set(compiled)
    for kind, arities in compiled.items():
        assert kind in static, f"'{kind}' compiled, WARM001 does not list it"
        assert not static[kind] or set(arities) <= set(static[kind]), (kind, arities, static[kind])


def test_one_extras_row_takes_its_batch_to_single_steps():
    greedy = batch_of(dict())
    sched = mk_sched()
    toks, _ = serve(sched, greedy + [("lp", list(range(5, 23)), 12, dict(logprobs=True), None)])
    kinds = decode_step_kinds(sched)
    assert kinds and set(kinds) == {"decode"}, kinds
    for req in greedy:
        alone = mk_sched()
        alone_toks, _ = serve(alone, [req])
        assert set(decode_step_kinds(alone)) == {"decode_multi"}
        assert toks[req[0]] == alone_toks[req[0]]


def test_windows_under_the_megakernel_emit_the_gather_path_tokens():
    """What a chip runs (``attention_impl`` ``auto`` is the megakernel there, the gather here):
    the ragged kernel inside ``decode_multi``'s window loop, interpreted."""
    requests = batch_of(dict(), max_tokens=18)
    mega = mk_sched(CFG.replace(attention_impl="megakernel"), decode_buckets=[4], prefill_buckets=[32],
                    enable_mixed_batching=False)
    mega.warmup(ctx_tokens=64)
    mega.flight.mark_warmup_done(warmed=True)
    toks, _ = serve(mega, requests)
    assert set(decode_step_kinds(mega)) == {"decode_multi"}
    assert mega.flight.compiles_after_warmup_total == 0, mega.flight.post_warmup_keys
    assert toks == serve(mk_sched(CFG.replace(attention_impl="gather")), requests)[0]


# A prompt of 20 and 26 tokens out end at 46: windows of 8 carry 21 -> 29 -> 37 -> 45, and the
# next one (to 53) passes a max_seq_len of 48, or asks a fourth block of a pool that has three.
TIGHT = {
    "max_seq_len": (CFG.replace(max_seq_len=48), dict()),
    "out_of_blocks": (CFG, dict(num_blocks=4)),
}


@pytest.mark.parametrize("why", list(TIGHT))
def test_a_window_that_cannot_be_reserved_finishes_in_single_steps(why):
    cfg, settings = TIGHT[why]
    request = [("a", list(range(1, 21)), 26, dict(), None)]
    tight = mk_sched(cfg, **settings)
    toks, fin = serve(tight, request)
    roomy = mk_sched()
    assert (toks, fin) == serve(roomy, request)
    assert fin == {"a": "length"} and len(toks["a"]) == 26
    assert decode_step_kinds(roomy) == ["decode_multi"] * 4
    assert decode_step_kinds(tight) == ["decode_multi"] * 3 + ["decode"]
    assert tight.preempt_total == 0 and tight.flight.log.step <= 8


def readme_step_programs():
    """{kind: the cell of the column "`Scheduler.warmup` registers it"} of README "Step programs"."""
    with open(os.path.join(REPO, "README.md")) as f:
        section = f.read().split("## Step programs\n")[1].split("\n## ")[0]
    rows = [[c.strip() for c in line.strip().strip("|").split("|")] for line in section.splitlines()
            if line.startswith("| `")]
    return {r[0].strip("`"): r[3] for r in rows}


@pytest.mark.parametrize("preset", ["tiny", "tiny-moe", "tiny-eva", "tiny-hybrid"])
def test_warmup_registers_exactly_the_kinds_that_can_dispatch(preset):
    cfg = get_config(preset)
    sched = mk_sched(cfg, llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32), num_blocks=64,
                     decode_buckets=[4], prefill_buckets=[32],
                     enable_prefix_caching=True)  # the program's default (eva turns it off)
    assert sched.warmup(ctx_tokens=64) > 0
    table = readme_step_programs()
    assert len(table) == 10 and not GONE & set(table)
    only = "layer_types" if cfg.is_hybrid else cfg.attention_kind  # (layer_types: neither waves nor prefix blocks)
    expected = {kind for kind, when in table.items() if when == "yes" or when.startswith(f"yes, `{only}` only")}
    registered = {k[0] for k in sched.flight._exec_keys}
    assert registered == expected
    assert not registered & GONE


def _decode_programs(cfg, params):
    """(``decode``, ``decode_multi``) of ``cfg`` as jaxprs, on shapes alone."""
    from dynamo_tpu.engine.kv_cache import KvCacheArrays

    B, W, i32, f32 = 4, 4, jnp.int32, jnp.float32
    cache = KvCacheArrays.create(cfg, num_blocks=16, dtype=f32)
    io = (jnp.zeros((B,), i32), jnp.full((B,), 5, i32), jnp.ones((B, W), i32), jnp.ones((B,), bool))
    one = jax.make_jaxpr(lambda p, k, v: llama.decode(p, cfg, k, v, *io))(params, cache.k, cache.v)
    window = jax.make_jaxpr(lambda p, k, v: llama.decode_multi(
        p, cfg, k, v, *io, jnp.zeros((B,), f32), jnp.zeros((B,), i32), jnp.ones((B,), f32), jax.random.PRNGKey(1), 8,
    ))(params, cache.k, cache.v)
    return {"decode": one, "decode_multi": window}


@pytest.mark.parametrize("program", ["decode", "decode_multi"])
@pytest.mark.parametrize("preset", ["tiny", "tiny-moe", "tiny-eva"])
def test_decode_programs_do_not_depend_on_the_chunk_tile(preset, program, monkeypatch):
    """Every row of ``decode`` and ``decode_multi`` is a length-1 row: under the
    megakernel each launch walks the list of its rows' live pages — one axis
    of steps, its bound traced — and the traced program is the same whatever
    tile a chunk would take. (Against the parent commit, once: PERF.md
    section 6, PR 31.)"""
    from jax._src.pallas.core import dynamic_grid_dim

    from dynamo_tpu.engine.attention import megakernel as mk
    from tests.test_megakernel import _kernel_grids

    cfg = get_config(preset).replace(attention_impl="megakernel")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    traced = _decode_programs(cfg, params)[program]
    grids = _kernel_grids(traced.jaxpr)
    assert grids and set(grids) == {(dynamic_grid_dim,)}, grids  # the live items, not B rows x (W pages + the fresh keys)
    monkeypatch.setattr(mk, "TILE_MAX", 16)
    monkeypatch.setattr(mk, "TILE_MIN", 2)
    mk.ragged_paged_attention.clear_cache()
    assert str(_decode_programs(cfg, params)[program]) == str(traced)
