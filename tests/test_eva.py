"""attention_kind "eva" on the served path: EVA chunked linear attention with
the summaries of rolled windows and the current window's exact keys in one
paged pool. At a small size (window 32, chunk 4, 2 layers, 4 heads), seeded
weights, against the plain reference ``benchmark/families/evabyte_reference``
(which imports nothing of the program): logits where a program returns them,
and for what the scheduler serves the reference's logit of each token it chose
(a tie on rounding cannot fail it, a wrong row does)."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import parity  # noqa: E402
from benchmark.families import evabyte, evabyte_reference  # noqa: E402
from dynamo_tpu.engine.config import ModelConfig, get_config  # noqa: E402
from dynamo_tpu.engine.kv_cache import cache_rows  # noqa: E402
from dynamo_tpu.engine.models import llama  # noqa: E402
from dynamo_tpu.engine.sampling import SamplingParams  # noqa: E402
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions  # noqa: E402

CFG = get_config("tiny-eva")  # window 32, chunk 4 (8 summaries a window), block 8, 2 layers, 4 heads
W, M = CFG.window_size, CFG.summaries_per_window


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


# --- position and cache row -------------------------------------------------------


@pytest.mark.parametrize("window,chunk", [(32, 4), (2048, 16), (64, 64), (48, 6)])
def test_rows_below_a_query_are_its_window_keys_and_earlier_summaries(window, chunk):
    """For random t: the table laid out by ``cache_rows``, read up to t's own
    row, is the reference's S (exact keys of t's window up to t) and R (one
    summary per chunk of every earlier window), each exactly once."""
    c = CFG.replace(window_size=window, chunk_size=chunk)
    m = window // chunk
    rng = np.random.default_rng(window)
    for t in [0, window - 1, window, 2 * window - 1, 2 * window] + rng.integers(0, 20 * window, size=200).tolist():
        w = t // window
        S = [j for j in range(w * window, t + 1)]
        R = [ch for ch in range((t + 1 + chunk - 1) // chunk + m) if (chunk * ch) // window < w]
        rows_of_S = [int(cache_rows(c, j)) for j in S]
        rows_of_R = [m * ((chunk * ch) // window) + (chunk * ch % window) // chunk for ch in R]  # where a roll writes chunk ch
        assert sorted(rows_of_R + rows_of_S) == list(range(int(cache_rows(c, t)) + 1))
        assert rows_of_S == list(range(m * w, m * w + len(S)))  # a window's rows are consecutive
    ts = jnp.asarray(rng.integers(0, 20 * window, size=64))
    assert np.array_equal(np.asarray(cache_rows(c, ts)), [cache_rows(c, int(t)) for t in ts])  # traced as on the host
    assert cache_rows(get_config("tiny"), 77) == 77  # causal: the row is the position


def test_config_refuses_what_eva_cannot_be():
    for bad in (dict(chunk_size=5), dict(window_size=0), dict(kv_cache_dtype="int8"), dict(architecture="mla")):
        with pytest.raises(ValueError):
            CFG.replace(**bad)
    with pytest.raises(ValueError):
        get_config("tiny").replace(attention_kind="windowed")
    with pytest.raises(ValueError):
        get_config("tiny").replace(num_pred_heads=2, tie_word_embeddings=True)
    assert isinstance(CFG, ModelConfig) and CFG.is_eva and M == 8


# --- the step programs against the reference, logits ------------------------------

SPEC = {"prompt_lens": [12, 30, 70, 29], "chunk": 16, "window": 4, "windows": 2, "decode_bucket": 4,
        "limit_rel_err": 0.02, "limit_group_rel_err": 0.03}


@pytest.mark.parametrize("block_size,impl,prefill_impl", [
    (8, "gather", "auto"), (16, "gather", "auto"), (8, "megakernel", "auto"), (8, "paged", "auto"), (8, "paged", "flash")],
    ids=["block8", "block16-a-window-begins-inside-a-block", "megakernel-interpreted", "paged-interpreted",
         "paged-and-flash-chunks-interpreted"])
def test_step_programs_agree_with_the_reference_across_rolls(params, block_size, impl, prefill_impl):
    """prefill, a mixed step per chunk with earlier sequences riding as decode
    rows, the roll program, and decode_multi windows, on one paged pool: a
    prompt through two rolls, chunks ending exactly on a boundary, a decode
    row whose step completes a window and the first step after its roll, a
    window row that stops at its boundary. Every control fails its limit.
    ``paged`` with flash chunks is what the benchmark's configuration serves."""
    c = CFG.replace(block_size=block_size, attention_impl=impl, prefill_impl=prefill_impl)
    controls = evabyte.CONTROLS if impl == "gather" and block_size == 8 else ()
    r = parity.check(evabyte, params, c, 5, SPEC, controls=controls, fault=bool(controls))
    assert r["ok"], {k: r[k] for k in ("rel_err", "group_rel_err", "worst_group", "sampled_is_argmax")}
    assert {"chunk_fresh", "chunk_window", "chunk_summaries", "mixed_decode", "decode_rolled", "window_s3"} <= set(r["groups"])
    for name in controls:
        assert r["controls"][name]["fails"], (name, r["controls"][name])
    if controls:
        assert r["fault_control"]["fails"]  # the tables rolled, the roll program never ran


# kind of rows -> (configuration, where a chunk with a prefix starts): a causal row's prefix ends inside a page (21 rows
# of pages of 16); an eva row's is the summaries of one or of three rolled windows (a block each) and the first 8 rows
# of its own window. A fresh chunk starts at 0 whatever the kind, so three summary blocks bring no fresh case of their own.
CHUNK_ROWS = {"causal": ("tiny", 21), "eva-1-summary-block": ("tiny-eva", W + 8), "eva-3-summary-blocks": ("tiny-eva", 3 * W + 8)}
CHUNK_CASES = [(rows, prefix, valid, pad) for rows in CHUNK_ROWS for prefix in (False, True) for valid in (16, 9) for pad in (0, 3)
               if prefix or rows != "eva-3-summary-blocks"]


@pytest.mark.parametrize("rows,prefix,valid,pad", CHUNK_CASES, ids=[
    f"{rows}-{'prefix' if prefix else 'fresh'}-{valid}-of-16-{'padded-table' if pad else 'exact-table'}" for rows, prefix, valid, pad in CHUNK_CASES])
def test_a_chunk_walks_tiles_wherever_a_kernel_serves_the_pool(rows, prefix, valid, pad):
    """``attention_impl="paged"`` (honoured off the TPU, its kernels interpreted):
    a chunk in ``prefill`` and in ``mixed_step`` takes the megakernel's tile walk
    (``llama.chunk_walks_tiles``) and the decode rows beside it the paged kernel,
    and both give the logits and the pool rows of the ``gather`` path, within
    tests/test_megakernel.py's tolerances: over a pool of random rows, a chunk
    that fills its bucket of 16 or 9 of it, a table as wide as its rows or with
    three padded slots, a live decode row and a dead lane."""
    from dynamo_tpu.engine.kv_cache import KvCacheArrays

    preset, start = CHUNK_ROWS[rows]
    base = get_config(preset)
    weights = llama.init_params(base, jax.random.PRNGKey(0), dtype=jnp.float32)
    start = start if prefix else 0
    bs, rng = base.block_size, np.random.default_rng(valid + pad + start)
    blocks = -(-(int(cache_rows(base, start)) + 16) // bs)
    ids = rng.permutation(np.arange(1, 32))
    table = jnp.asarray(np.r_[ids[:blocks], np.zeros(pad, np.int64)].astype(np.int32))
    d_tables = jnp.asarray(np.stack([np.r_[ids[blocks : blocks + 2], 0], np.zeros(3, np.int64)]).astype(np.int32))
    chunk = np.zeros(16, np.int32)
    chunk[:valid] = rng.integers(1, 255, size=valid)
    pool = KvCacheArrays.create(base, num_blocks=32, dtype=jnp.float32)
    k0, v0 = (jax.random.normal(jax.random.PRNGKey(i), pool.k.shape, jnp.float32) for i in (1, 2))

    def run(impl):
        c = base.replace(attention_impl=impl)
        assert llama.chunk_walks_tiles(c, k0) == (impl == "paged")

        def both(p, k, v):
            lg, k1, v1 = llama.prefill(p, c, k, v, jnp.asarray(chunk), jnp.int32(valid), jnp.int32(start), table)
            mixed = llama.mixed_step(p, c, k, v, jnp.asarray(chunk), jnp.int32(valid), jnp.int32(start), table,
                                     jnp.asarray([7, 0], jnp.int32), jnp.asarray([bs + 3, 0], jnp.int32), d_tables, jnp.asarray([True, False]))
            return lg, k1, v1, mixed[0][:2], mixed[1], mixed[2]  # logits: the chunk's row and the live decode row's

        return jax.jit(both)(weights, k0, v0)

    for name, want, got in zip(("prefill logits", "prefill k", "prefill v", "mixed logits", "mixed k", "mixed v"), run("gather"), run("paged")):
        want, got = np.asarray(want), np.asarray(got)
        if name[-1] in "kv":  # pool rows but the scratch block's: dead rows sink there, and nothing reads it
            np.testing.assert_allclose(got[:, 1:], want[:, 1:], atol=2e-5, err_msg=name)
        else:
            np.testing.assert_allclose(got, want, atol=2e-4, err_msg=name)


def test_the_window_kernels_steps_past_a_boundary_write_nothing(params):
    """decode_multi: a row that reaches its window boundary inside the window
    keeps the completed window's rows as they are (the roll reads them next)."""
    from dynamo_tpu.engine.kv_cache import KvCacheArrays

    cache = KvCacheArrays.create(CFG, 8, dtype=jnp.float32)
    k = cache.k + 1.0  # rows of a completed window, anything but zero
    table = jnp.asarray([[1, 2, 3, 4, 5, 0]], jnp.int32)
    z = jnp.zeros((1,), jnp.float32)
    out, k2, _ = llama.decode_multi(params, CFG, k, cache.v + 1.0, jnp.asarray([5]), jnp.asarray([W - 2]), table,
                                    jnp.asarray([True]), z, jnp.zeros((1,), jnp.int32), z + 1, jax.random.PRNGKey(0), 4)
    written = np.argwhere(np.any(np.asarray(k2 != k), axis=(0, 3)))  # (block, offset) pairs that changed
    bs = CFG.block_size
    assert sorted(map(tuple, written.tolist())) == [(0, 0), (table[0, (W - 2) // bs], (W - 2) % bs),
                                                    (table[0, (W - 1) // bs], (W - 1) % bs)]  # scratch, and two rows


# --- through the scheduler --------------------------------------------------------


def serve(params, requests, *, num_blocks=48, arrive_at=None):
    """Run ``requests`` {id: (prompt, max_tokens)} through a Scheduler to the
    end; ``arrive_at[id]`` is the iteration before which a request arrives."""
    sc = SchedulerConfig(num_blocks=num_blocks, max_running=4, prefill_buckets=[16], decode_buckets=[4],
                         max_prefill_chunk=16, mixed_prefill_budget=16, num_scheduler_steps=4)
    s = Scheduler(CFG, params, sc, dtype=jnp.float32)
    out = {rid: [] for rid in requests}
    arrive_at = arrive_at or {}
    step = 0
    while step == 0 or s.has_work() or any(v >= step for v in arrive_at.values()):
        for rid, (prompt, n) in requests.items():
            if arrive_at.get(rid, 0) == step:
                s.add_request(rid, prompt, SamplingParams(temperature=0.0), StopConditions(max_tokens=n, ignore_eos=True))
        for seq, o in s.step():
            if o.token_id >= 0:
                out[seq.request_id].append(o.token_id)
        step += 1
        bs = CFG.block_size
        for q in s.running + s.waiting:  # a live table holds its rows and not a block more than a window's reserve
            if q.block_ids:
                held = q.total_len - 1 if q.state.value == "running" else q.num_computed
                assert len(q.block_ids) * bs >= s._rows_for(q, held)
                assert len(q.block_ids) <= math.ceil((M * q.rolls + W) / bs) + 1
        assert step < 400
    return s, out


def assert_served_as_the_reference(params, requests, out):
    for rid, (prompt, n) in requests.items():
        assert len(out[rid]) == n
        full = np.asarray(list(prompt) + out[rid])
        lg = evabyte_reference.forward(params, CFG, [full[:-1]], [list(range(len(prompt) - 1, len(full) - 1))])[0]
        gap = [float(lg[i].max() - lg[i][tok]) for i, tok in enumerate(out[rid])]
        assert max(gap) < 1e-3, (rid, int(np.argmax(gap)), max(gap))


def prompts(*lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, size=n).tolist() for n in lens]


CASES = {
    # name: (requests {id: (prompt length, answer length)}, arrivals, blocks, what must have happened)
    "one-roll-in-prefill": ({"a": (40, 6)}, {}, 48, dict(rolls=1)),
    "two-rolls-in-prefill-chunks-end-on-the-boundary": ({"a": (70, 6)}, {}, 48, dict(rolls=2)),
    "prompt-ends-exactly-on-the-boundary": ({"a": (32, 6)}, {}, 48, dict(rolls=1)),
    "a-decode-row-rolls": ({"a": (30, 12)}, {}, 48, dict(rolls=1)),
    "a-decode-row-rolls-while-a-prompt-rides": ({"a": (30, 12), "b": (70, 10), "c": (32, 40)}, {"b": 1, "c": 1}, 48,
                                                dict(rolls=5)),
    "two-sequences-roll-in-one-step": ({"a": (28, 10), "b": (29, 10)}, {}, 48, dict(rolls=2, together=2)),
    "preempted-and-recomputed": ({"a": (20, 30), "b": (40, 30), "c": (25, 30)}, {}, 12, dict(preempted=True)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_scheduler_serves_eva_exactly(params, case):
    """Prefill chunks cut at window boundaries, rolls as a phase of the step,
    multi-step windows stopped at a boundary, preemption by recompute: what
    the scheduler serves is what the reference's own greedy decoding gives,
    and every block goes back to the allocator."""
    shape, arrive_at, blocks, want = CASES[case]
    ps = prompts(*(n for n, _ in shape.values()))
    requests = {rid: (p, n) for (rid, (_, n)), p in zip(shape.items(), ps)}
    s, out = serve(params, requests, num_blocks=blocks, arrive_at=arrive_at)
    assert_served_as_the_reference(params, requests, out)
    assert len(s.allocator._free) == blocks - 1  # all but the scratch block
    rolls = [a for n, _, _, _, a in s.flight.log.spans if n == "sched.roll"]
    bs = CFG.block_size
    for a in rolls:  # after a roll: the summaries and one row of the new window, nothing else
        assert a["blocks"] == math.ceil((M * (a["window"] + 1) + 1) / bs) and a["released"] >= 1
    assert s.eva_released_blocks_total == sum(a["released"] for a in rolls)
    if "rolls" in want:
        assert s.eva_rolls_total == len(rolls) == want["rolls"] and s.preempt_total == 0
    steps = [a for n, _, _, _, a in s.flight.log.spans if n == "sched.step" and a]
    if "together" in want:
        assert max(a.get("rolls", 0) for a in steps) == want["together"]
    if want.get("preempted"):
        assert s.preempt_total >= 1 and s.eva_rolls_total > len(requests)  # the recompute rolled again
    decode = [a for a in steps if a.get("kind") in ("decode_multi", "mixed", "decode")]
    assert decode and all("attended" in a and a["attended"] <= a["ctx"] for a in decode)
    assert any(a["attended"] < a["ctx"] for a in decode) == (s.eva_rolls_total > 0)
    for a in (a for a in decode if a["kind"] == "decode_multi"):
        # a window's entry counts the steps its rows took (a row stops at its boundary) and the rows they attended
        n, w, t = a["rows"], a["key"][0], a["decode"]
        assert 1 <= a["live_steps"] <= w and a["live_steps"] <= t <= n * a["live_steps"]
        assert t <= a["attended_sum"] <= (a["attended"] + n * w) * a["live_steps"]
        if t == n * w:  # no row stopped: every row grew by one a step
            assert a["attended_sum"] == w * a["attended"] + n * w * (w - 1) // 2
    if case == "a-decode-row-rolls":
        assert any(a["decode"] < a["rows"] * a["key"][0] for a in decode if a["kind"] == "decode_multi")
    gauges = s.kv_gauges()
    assert gauges["eva_rolls_total"] == s.eva_rolls_total and gauges["eva_summary_blocks"] == 0  # nothing is live
    assert "eva_rolls_total" in s.debug_state()["block_pool"]


def test_gauges_while_a_rolled_sequence_is_live(params):
    s, _ = serve(params, {}, num_blocks=48)
    (p,) = prompts(70)
    s.add_request("a", p, SamplingParams(temperature=0.0), StopConditions(max_tokens=4, ignore_eos=True))
    while not s.running:
        s.step()
    g, (seq,) = s.kv_gauges(), s.running
    assert seq.rolls == 2 and g["eva_summary_blocks"] == 2 and g["eva_window_blocks"] == len(seq.block_ids) - 2
    info = s.debug_state()["running"][0]
    assert info["rolls"] == 2 and info["cache_rows"] == cache_rows(CFG, seq.total_len - 1) + 1 == 2 * M + 71 - 2 * W
    assert 0.0 <= g["kv_fragmentation"] < 1.0 and s.config_snapshot()["model"]["attention_kind"] == "eva"


# --- refusals ----------------------------------------------------------------------


def _bare(params):
    return Scheduler(CFG, params, SchedulerConfig(num_blocks=16), dtype=jnp.float32)


REFUSALS = {
    "kvbm": lambda s, p: s.attach_kvbm(object()),
    "speculation": lambda s, p: s.attach_draft(get_config("tiny"), None),
    "speculation-with-an-eva-draft": lambda s, p: Scheduler(
        get_config("tiny"), llama.init_params(get_config("tiny"), jax.random.PRNGKey(0)),
        SchedulerConfig(num_blocks=16)).attach_draft(CFG, p),
    "export": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(), keep_blocks_on_finish=True),
    "injection": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(), prefilled={"blocks": []}),
    "take-export": lambda s, p: s.take_export("x"),
    "take-export-device": lambda s, p: s.take_export_device("x"),
    "multimodal": lambda s, p: s.add_request("x", [1, 2], SamplingParams(), StopConditions(),
                                            mm_features=np.zeros((1, 64), np.float32)),
    "prefix-registration": lambda s, p: (setattr(s.sc, "enable_prefix_caching", True),
                                         s._register_full_blocks(type("S", (), {"block_hashes": [1]})())),
    "prefix-matching": lambda s, p: s._match_prefix_tiers(None),
    "wave-admission-program": lambda s, p: llama.chunk_decode(p, CFG, None, None, jnp.zeros((1, 4), jnp.int32), None, None, None),
    "embeddings-program": lambda s, p: llama.embed(p, CFG, jnp.zeros((4,), jnp.int32), 4),
    "a-mesh": lambda s, p: Scheduler(CFG, p, SchedulerConfig(num_blocks=16), mesh=object()),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_equates_position_and_row_is_refused_for_eva(params, what):
    s = _bare(params)
    with pytest.raises(NotImplementedError, match="attention_kind='eva'"):
        REFUSALS[what](s, params)


def test_eva_scheduler_turns_off_what_it_refuses(params):
    s = _bare(params)
    assert s.sc.enable_prefix_caching is False and not s._supports_chunk_admit
    assert s.max_blocks_per_seq == math.ceil((M * ((CFG.max_seq_len - 1) // W) + W) / CFG.block_size)
    assert s.warmup(ctx_tokens=64) > 0 and ("eva_roll",) in s.flight._exec_keys  # the roll program is warmed


# --- parameters, tokenizer -----------------------------------------------------------


def test_init_params_draws_what_the_layer_needs():
    p = llama.init_params(CFG, jax.random.PRNGKey(1), dtype=jnp.float32)
    L = CFG.num_layers
    assert p["layers"]["eva_mu"].shape == p["layers"]["eva_phi"].shape == (L, CFG.num_kv_heads, CFG.head_dim)
    assert p["lm_head"].shape == (CFG.hidden_size, CFG.vocab_size * CFG.num_pred_heads)
    assert not np.any(np.asarray(p["final_norm"])) and not np.any(np.asarray(p["layers"]["attn_norm"]))  # 1 + g, g = 0
    assert "eva_mu" not in llama.init_params(get_config("tiny"), jax.random.PRNGKey(1))["layers"]


def test_byte_tokenizer_offset():
    from dynamo_tpu.llm.tokenizer import ByteTokenizer, load_tokenizer

    plain, eva = ByteTokenizer(), load_tokenizer("bytes:64")
    text = "naïve café\n"
    assert eva.vocab_size == 320 and eva.encode("A") == [64 + 65] and eva.decode(eva.encode(text)) == text
    assert eva.decode([3, 64 + 72, 63, 64 + 105]) == "Hi"  # special ids carry no text
    assert plain.vocab_size == 256 and plain.encode("A") == [65] and plain.decode(plain.encode(text)) == text
    with pytest.raises(ValueError):
        ByteTokenizer(-1)
