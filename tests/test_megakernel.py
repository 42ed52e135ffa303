"""Ragged paged-attention megakernel: interpreter-mode parity vs the XLA
gather path over head layouts (GQA/MQA/MHA), ragged edge cases (length-1
decode rows mixed with chunk rows, short sequences in wide buckets, page-
boundary prefix lengths, dead scratch-block-0 slots), the int8-KV
dequant-in-VMEM path, and the fused N-step decode window (token AND KV
cache-content parity vs ``decode_multi``, exactly ONE pallas launch per
window, 0 post-warmup compiles at the scheduler).

Everything runs the Pallas interpreter on CPU (tier-1 CI); the kernels are
the same code the TPU auto-selection dispatches.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.attention import megakernel as mk
from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

CFG = get_config("tiny")  # GQA: 4 heads over 2 KV heads
MEGA = CFG.replace(attention_impl="megakernel")


def _fresh(cfg, num_blocks=64):
    c = KvCacheArrays.create(cfg, num_blocks=num_blocks, dtype=jnp.float32)
    return c.k, c.v


def _prefill(params, cfg, k, v, toks, table, cache_len=0):
    t = jnp.asarray(np.asarray(toks, np.int32))
    return jax.jit(
        lambda p, k, v: llama.prefill(
            p, cfg, k, v, t, jnp.int32(len(toks)), jnp.int32(cache_len), table
        )
    )(params, k, v)


# ---------------------------------------------------------------------------
# Head layouts: GQA / MHA / MQA
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kvh", [2, 4, 1], ids=["gqa", "mha", "mqa"]
)
def test_decode_parity_head_layouts(kvh):
    """Megakernel decode logits + written KV match the XLA gather for every
    head layout the block-diagonal GQA fold must cover."""
    base = CFG.replace(num_kv_heads=kvh)
    params = llama.init_params(base, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(1)
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32))
    toks = rng.integers(1, 255, size=30)

    B = 3
    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    pos = jnp.full((B,), 30, jnp.int32)
    tables_d = jnp.asarray(np.tile(np.arange(1, 5, dtype=np.int32), (B, 1)))
    active = jnp.ones((B,), bool)

    def run(cfg):
        k, v = _fresh(cfg)
        _, k, v = _prefill(params, cfg, k, v, toks, table)
        return jax.jit(
            lambda p, k, v: llama.decode(p, cfg, k, v, dtoks, pos, tables_d, active)
        )(params, k, v)

    lg_g, kg, vg = run(base)
    lg_m, km, vm = run(base.replace(attention_impl="megakernel"))
    np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_m), atol=2e-4)
    np.testing.assert_allclose(np.asarray(kg), np.asarray(km), atol=2e-5)
    np.testing.assert_allclose(np.asarray(vg), np.asarray(vm), atol=2e-5)


# ---------------------------------------------------------------------------
# Ragged edge cases
# ---------------------------------------------------------------------------


def test_prefill_chunk_with_prefix_parity():
    """A (start, len) chunk row over a cached prefix — including a chunk
    that starts exactly ON a page boundary — matches the gather path."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(2)
    table = jnp.asarray(np.arange(1, 6, dtype=np.int32))
    first = rng.integers(1, 255, size=32)  # ends exactly at 2 pages (bs=16)
    second = rng.integers(1, 255, size=19)

    def run(cfg):
        k, v = _fresh(cfg)
        lg1, k, v = _prefill(params, cfg, k, v, first, table)
        lg2, k, v = _prefill(params, cfg, k, v, second, table, cache_len=32)
        return lg1, lg2, k, v

    g1, g2, kg, vg = run(CFG)
    m1, m2, km, vm = run(MEGA)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(m1), atol=2e-4)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(m2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(kg), np.asarray(km), atol=2e-5)


def test_mixed_step_parity_chunk_plus_decode_rows():
    """The whole mixed step — a ragged chunk row AND length-1 decode rows in
    one launch — matches the two-shape XLA path, including padded chunk
    queries (len < bucket) and an INACTIVE decode lane. Scratch block 0 is
    excluded from the KV comparison: dead rows sink different garbage
    there by design and it is never handed out or read."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(3)
    toks = rng.integers(1, 255, size=21)  # short seq: 21 tokens in 2 pages
    p_table = jnp.asarray(np.array([5, 6, 7, 8], np.int32))

    B = 4  # 3 live decode rows + 1 dead lane
    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    dpos = jnp.asarray(np.array([30, 16, 7, 0], np.int32))  # incl. page-exact 16
    # Wide bucket for a short row: row 2 (7 tokens) rides an 8-wide table.
    tables_d = jnp.asarray(
        np.stack([np.r_[1:5, 0, 0, 0, 0], np.r_[9:13, 0, 0, 0, 0],
                  np.r_[13:17, 0, 0, 0, 0], np.zeros(8, np.int64)]).astype(np.int32)
    )
    active = jnp.asarray(np.array([True, True, True, False]))

    chunk = np.zeros((16,), np.int32)
    chunk[:9] = rng.integers(1, 255, size=9)

    # Fixed prompts so both impls seed bit-identical caches. The chunk
    # sequence's 21-token cached prefix (toks above) lives at blocks 5-8.
    seed_prompts = [
        (toks, np.arange(5, 9)),
        (rng.integers(1, 255, size=30), np.arange(1, 5)),
        (rng.integers(1, 255, size=16), np.arange(9, 13)),
        (rng.integers(1, 255, size=7), np.arange(13, 17)),
    ]

    def run(cfg):
        k, v = _fresh(cfg)
        for toks_s, tbl in seed_prompts:
            _, k, v = _prefill(params, cfg, k, v, toks_s,
                               jnp.asarray(tbl.astype(np.int32)))
        return jax.jit(
            lambda p, k, v: llama.mixed_step(
                p, cfg, k, v, jnp.asarray(chunk), jnp.int32(9), jnp.int32(21),
                p_table, dtoks, dpos, tables_d, active,
            )
        )(params, k, v)

    lg_g, kg, vg = run(CFG)
    lg_m, km, vm = run(MEGA)
    # Live rows only: logits row 0 is the chunk, rows 1..3 the live decode
    # lanes. The dead lane's logits are garbage in BOTH impls (masked
    # softmax junk vs kernel zeros) and the scheduler never reads them.
    np.testing.assert_allclose(np.asarray(lg_g)[:4], np.asarray(lg_m)[:4], atol=2e-4)
    np.testing.assert_allclose(np.asarray(kg)[:, 1:], np.asarray(km)[:, 1:], atol=2e-5)
    np.testing.assert_allclose(np.asarray(vg)[:, 1:], np.asarray(vm)[:, 1:], atol=2e-5)


def test_dead_queries_return_zeros():
    """Dead ragged rows (meta active=0) read nothing and return exact zeros
    from the kernel — the pl.when skip, not masked softmax garbage."""
    kvh, hd, bs = 2, 16, 16
    H = 4
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((3, H, hd)).astype(np.float32))
    ke = jnp.asarray(rng.standard_normal((3, kvh, hd)).astype(np.float32))
    # Pages in the pool's layout: heads merged into lanes (KvCacheArrays).
    k_pages = jnp.asarray(rng.standard_normal((6, bs, kvh * hd)).astype(np.float32))
    v_pages = jnp.asarray(rng.standard_normal((6, bs, kvh * hd)).astype(np.float32))
    tables = jnp.asarray(np.array([[1, 2], [3, 4], [0, 0]], np.int32))
    meta = mk.build_meta(
        jnp.asarray(np.array([0, 1, 2], np.int32)),
        jnp.asarray(np.array([20, 20, 0], np.int32)),
        jnp.asarray(np.array([0, 1, 2], np.int32)),
        jnp.asarray(np.array([1, 2, 2], np.int32)),  # row 2: no fresh keys either
        jnp.asarray(np.array([1, 1, 0], np.int32)),  # row 2 dead
    )
    out = mk.ragged_paged_attention(
        q, ke, ke, k_pages, v_pages, tables, meta,
        num_kv_heads=kvh, block_size=bs, interpret=True,
    )
    assert np.all(np.asarray(out)[2] == 0.0), "dead query must return zeros"
    assert np.all(np.isfinite(np.asarray(out)[:2]))


# ---------------------------------------------------------------------------
# int8 KV: dequant-in-VMEM path
# ---------------------------------------------------------------------------


def test_int8_kv_megakernel_parity():
    """Megakernel attention over a QuantKv cache (int8 codes + per-(token,
    head) scales dequantized in VMEM) matches the gather path reading the
    SAME quantized cache — bitwise-equal inputs, so tolerance is float
    accumulation, not quantization error."""
    cfg8_g = CFG.replace(kv_cache_dtype="int8")
    cfg8_m = cfg8_g.replace(attention_impl="megakernel")
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32))
    toks = rng.integers(1, 255, size=30)

    B = 2
    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    pos = jnp.full((B,), 30, jnp.int32)
    tables_d = jnp.asarray(np.tile(np.arange(1, 5, dtype=np.int32), (B, 1)))
    active = jnp.ones((B,), bool)

    def run(cfg):
        k, v = _fresh(cfg)
        _, k, v = _prefill(params, cfg, k, v, toks, table)
        lg, k, v = jax.jit(
            lambda p, k, v: llama.decode(p, cfg, k, v, dtoks, pos, tables_d, active)
        )(params, k, v)
        return lg

    lg_g = run(cfg8_g)
    lg_m = run(cfg8_m)
    np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_m), atol=5e-4)


def test_paged_int8_degrades_to_gather():
    """attention_impl='paged' + int8 KV no longer raises at config
    validation; the engine degrades to the gather with a warning."""
    cfg = CFG.replace(attention_impl="paged", kv_cache_dtype="int8")  # no raise
    cache = KvCacheArrays.create(cfg, num_blocks=8, dtype=jnp.float32)
    assert llama.resolve_attention_impl(cfg, cache.k) == "gather"
    # megakernel keeps the fused path for int8.
    cfg_m = CFG.replace(attention_impl="megakernel", kv_cache_dtype="int8")
    assert llama.resolve_attention_impl(cfg_m, cache.k) == "megakernel"


def test_attention_impl_validation():
    with pytest.raises(ValueError, match="attention_impl"):
        CFG.replace(attention_impl="bogus")
    for ok in ("auto", "gather", "paged", "megakernel"):
        assert CFG.replace(attention_impl=ok).attention_impl == ok


# ---------------------------------------------------------------------------
# Fused N-step decode window
# ---------------------------------------------------------------------------


def test_fused_window_parity_and_single_launch():
    """One fused launch serves an entire greedy decode window: tokens AND
    written KV cache contents match greedy ``decode_multi``, and the traced
    executable contains exactly ONE pallas_call site."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(6)
    B, steps = 3, 4
    toks = rng.integers(1, 255, size=21)
    tables = np.stack([np.arange(1 + 4 * b, 5 + 4 * b, dtype=np.int32) for b in range(B)])

    k, v = _fresh(CFG)
    for b in range(B):
        _, k, v = _prefill(params, CFG, k, v, toks, jnp.asarray(tables[b]))

    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    pos = jnp.full((B,), 21, jnp.int32)
    active = jnp.ones((B,), bool)
    t_j = jnp.asarray(tables)

    n0 = mk.trace_launch_count()
    toks_f, kf, vf = llama.decode_multi_fused(
        params, MEGA, k, v, dtoks, pos, t_j, active, num_steps=steps
    )
    assert mk.trace_launch_count() - n0 == 1, "fused window must be ONE launch"

    greedy = (jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
              jnp.ones((B,), jnp.float32))
    toks_r, kr, vr = jax.jit(
        lambda p, k, v: llama.decode_multi(
            p, CFG, k, v, dtoks, pos, t_j, active, *greedy,
            jax.random.PRNGKey(9), steps,
        )
    )(params, k, v)
    np.testing.assert_array_equal(np.asarray(toks_f), np.asarray(toks_r))
    np.testing.assert_allclose(
        np.asarray(kf)[:, 1:], np.asarray(kr)[:, 1:], atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(vf)[:, 1:], np.asarray(vr)[:, 1:], atol=2e-4
    )


def test_scheduler_fused_window_e2e():
    """Scheduler end-to-end with attention_impl='megakernel': greedy token
    streams match the gather scheduler, every decode window dispatches as
    ONE pallas launch (flight-recorder gauge == 1), and a warmed scheduler
    compiles NOTHING mid-traffic."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

    def run(impl, warm):
        sched = Scheduler(CFG.replace(attention_impl=impl), params, SchedulerConfig(
            num_blocks=128, max_running=4,
            prefill_buckets=[32], decode_buckets=[1, 2, 4],
            num_scheduler_steps=8, enable_prefix_caching=False,
            enable_overlap_decode=False, enable_mixed_batching=False,
        ), dtype=jnp.float32)
        if warm:
            sched.warmup(ctx_tokens=64)
            sched.flight.mark_warmup_done(warmed=True)
        toks = {}
        for i in range(3):
            sched.add_request(f"r{i}", list(range(1 + i, 25 + i)),
                              SamplingParams(temperature=0.0),
                              StopConditions(max_tokens=18, ignore_eos=True))
        for _ in range(200):
            if not sched.has_work():
                break
            for s, o in sched.step():
                if o.token_id >= 0:
                    toks.setdefault(s.request_id, []).append(o.token_id)
        return sched, toks

    s_m, t_m = run("megakernel", warm=True)
    s_g, t_g = run("gather", warm=False)
    assert t_m == t_g, "megakernel scheduler must emit identical greedy tokens"
    assert s_m._use_fused_window
    assert s_m.flight.fused_windows_total > 0
    assert s_m.flight.fused_window_pallas_launches == 1
    assert s_m.flight.compiles_after_warmup_total == 0, (
        f"post-warmup compiles: {s_m.flight.post_warmup_keys}"
    )
    stats = s_m.flight.to_stats()
    assert stats["fused_window_pallas_launches"] == 1
    assert stats["fused_windows_total"] == s_m.flight.fused_windows_total


# ---------------------------------------------------------------------------
# Flight recorder: paged-path cost model + mixed-step phase split
# ---------------------------------------------------------------------------


def test_cost_model_paged_vs_gather_bytes():
    from dynamo_tpu.engine.flight_recorder import StepCostModel

    gather = StepCostModel(1000, 2000, 10.0, peak_flops=1e12, peak_bw=1e11,
                           kv_read_factor=3.0)
    paged = StepCostModel(1000, 2000, 10.0, peak_flops=1e12, peak_bw=1e11,
                          kv_read_factor=1.0)
    fg, bg = gather.step_cost(4, 100)
    fp, bp = paged.step_cost(4, 100)
    assert fg == fp  # FLOPs don't depend on the attention path
    # gather: 2000 + 3*100*10 + 4*10; paged: 2000 + 100*10 + 4*10
    assert bg - bp == pytest.approx(2 * 100 * 10.0)
    # A decode_multi window streams params once per step; the fused window
    # streams them once per window.
    _, b_loop = paged.step_cost(32, 800, param_passes=8.0)
    _, b_fused = paged.step_cost(32, 100, param_passes=1.0)
    assert b_loop - b_fused == pytest.approx(7 * 2000 + 700 * 10.0)


def test_mixed_step_phase_split():
    """record_mixed_step books the chunk into the prefill roofline and the
    decode rows into decode — both gauges move, and the mixed histogram
    still counts the step."""
    from dynamo_tpu.engine.flight_recorder import FlightRecorder, StepCostModel

    fr = FlightRecorder()
    fr.set_cost_model(StepCostModel(10_000, 20_000, 64.0,
                                    peak_flops=1e12, peak_bw=1e11))
    fr.record_mixed_step(0.01, prefill_tokens=128, decode_tokens=8,
                         kv_read_prefill=256, kv_read_decode=4096)
    util = fr.utilization()
    assert util["prefill"][0] > 0 and util["decode"][1] > 0
    assert "mixed" not in util  # cost split entirely into the real phases
    stats = fr.to_stats()
    assert stats["step_mixed_steps_total"] == 1
    assert stats["step_mixed_tokens_total"] == 136
    assert stats["step_prefill_flops_total"] > 0
    assert stats["step_decode_bytes_total"] > 0


# ---------------------------------------------------------------------------
# In-kernel sampling epilogue: fused window vs the sync uniforms replay
# ---------------------------------------------------------------------------


def _window_uniforms(B, steps, seed=11):
    from dynamo_tpu.engine.sampling import make_window_uniforms

    return make_window_uniforms(
        jax.random.PRNGKey(seed),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool), steps,
    )


@pytest.mark.parametrize(
    "B,steps",
    [(8, 4), pytest.param(32, 2, marks=pytest.mark.slow)],
    ids=["b8", "b32"],
)
def test_fused_window_sampled_parity(B, steps):
    """The in-kernel sampling epilogue (temperature + top-k/top-p + inverse
    CDF) picks BIT-IDENTICAL tokens to ``decode_multi`` replaying the same
    uniforms, across mixed per-row params covering the threshold edges:
    greedy (temp 0), k=1 (degenerate top-k), p=1.0 (top-p off), k>vocab
    (clamps to full vocab), and plain temp>0. Written KV matches and the
    whole window is still ONE launch."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    toks = rng.integers(1, 255, size=21)
    tables = np.stack(
        [np.arange(1 + 4 * b, 5 + 4 * b, dtype=np.int32) for b in range(B)]
    )

    k, v = _fresh(CFG, num_blocks=4 * B + 2)
    for b in range(B):
        _, k, v = _prefill(params, CFG, k, v, toks, jnp.asarray(tables[b]))

    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    pos = jnp.full((B,), 21, jnp.int32)
    active = jnp.ones((B,), bool)
    t_j = jnp.asarray(tables)

    # Per-row params cycling through every filter edge the shared
    # _exact_thresholds reference must hold at.
    edge = [
        (0.0, 0, 1.0),      # greedy row -> one-hot dist, argmax pick
        (0.9, 1, 1.0),      # k=1: top-k degenerates to argmax
        (0.8, 0, 1.0),      # p=1.0: top-p off entirely
        (0.7, 999, 0.95),   # k > vocab: clamps to full vocab
        (1.3, 20, 0.9),     # plain joint top-k/top-p
    ]
    rows = [edge[i % len(edge)] for i in range(B)]
    temps = jnp.asarray([r[0] for r in rows], jnp.float32)
    tks = jnp.asarray([r[1] for r in rows], jnp.int32)
    tps = jnp.asarray([r[2] for r in rows], jnp.float32)
    unif = _window_uniforms(B, steps)

    n0 = mk.trace_launch_count()
    toks_f, kf, vf = llama.decode_multi_fused(
        params, MEGA, k, v, dtoks, pos, t_j, active, num_steps=steps,
        temps=temps, top_ks=tks, top_ps=tps, uniforms=unif, sampled=True,
    )
    assert mk.trace_launch_count() - n0 == 1, "sampled window must be ONE launch"

    toks_r, kr, vr = jax.jit(
        lambda p, k, v: llama.decode_multi(
            p, CFG, k, v, dtoks, pos, t_j, active, temps, tks, tps,
            jax.random.PRNGKey(9), steps, uniforms=unif,
        )
    )(params, k, v)
    np.testing.assert_array_equal(np.asarray(toks_f), np.asarray(toks_r))
    np.testing.assert_allclose(
        np.asarray(kf)[:, 1:], np.asarray(kr)[:, 1:], atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(vf)[:, 1:], np.asarray(vr)[:, 1:], atol=2e-4
    )


@pytest.mark.slow  # interpret-mode Pallas e2e; the CI `fused-sampling`
# job gates the same invariants through bench.py in its own budget
def test_scheduler_fused_sampled_e2e():
    """Warmed megakernel scheduler serves seeded temp>0 traffic entirely on
    the fused sampled window: the sampled-variant counter advances, ZERO
    post-warmup compiles over the enlarged (sampled) key space, and the
    same request seeds reproduce the same tokens on a fresh scheduler."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

    def run():
        sched = Scheduler(MEGA, params, SchedulerConfig(
            num_blocks=128, max_running=4,
            prefill_buckets=[32], decode_buckets=[1, 2, 4],
            num_scheduler_steps=8, enable_prefix_caching=False,
            enable_overlap_decode=False, enable_mixed_batching=False,
        ), dtype=jnp.float32)
        sched.warmup(ctx_tokens=64)
        sched.flight.mark_warmup_done(warmed=True)
        toks = {}
        for i in range(3):
            sched.add_request(
                f"r{i}", list(range(1 + i, 25 + i)),
                SamplingParams(temperature=0.8, top_k=20, top_p=0.9, seed=7 + i),
                StopConditions(max_tokens=10, ignore_eos=True),
            )
        for _ in range(200):
            if not sched.has_work():
                break
            for s, o in sched.step():
                if o.token_id >= 0:
                    toks.setdefault(s.request_id, []).append(o.token_id)
        return sched, toks

    s1, t1 = run()
    assert s1.flight.fused_sampled_windows_total > 0
    assert s1.flight.compiles_after_warmup_total == 0, (
        f"post-warmup compiles: {s1.flight.post_warmup_keys}"
    )
    assert all(len(v) == 10 for v in t1.values())
    _, t2 = run()
    assert t1 == t2, "seeded sampling on the fused path must be reproducible"


@pytest.mark.slow  # interpret-mode Pallas e2e; the CI `fused-sampling`
# job gates the same invariants through bench.py in its own budget
def test_scheduler_guided_fused_parity():
    """Guided rows ride the fused window (on-chip bitmask + next-state FSM
    advance) and emit the SAME schema-constrained tokens as the gather
    scheduler's host-FSM sync path — with zero post-warmup compiles."""
    from dynamo_tpu.llm.tokenizer import ByteTokenizer

    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    pattern = '\\{"city": "(SF|NY)"\\}'

    def run(impl, warm, steps):
        sched = Scheduler(CFG.replace(attention_impl=impl), params, SchedulerConfig(
            num_blocks=128, max_running=4,
            prefill_buckets=[32], decode_buckets=[1, 2, 4],
            num_scheduler_steps=steps, enable_prefix_caching=False,
            enable_overlap_decode=False, enable_mixed_batching=False,
            guided_pool_rows=64,
        ), dtype=jnp.float32, eos_token_ids=[0])
        sched.attach_guided(ByteTokenizer())
        if warm:
            sched.warmup(ctx_tokens=64)
            sched.flight.mark_warmup_done(warmed=True)
        toks = {}
        for i in range(2):
            sched.add_request(
                f"g{i}", list(range(5 + i, 21 + i)),
                SamplingParams(temperature=0.0), StopConditions(max_tokens=32),
                guided={"kind": "regex", "pattern": pattern},
            )
        for _ in range(300):
            if not sched.has_work():
                break
            for s, o in sched.step():
                if o.token_id >= 0:
                    toks.setdefault(s.request_id, []).append(o.token_id)
        return sched, toks

    s_m, t_m = run("megakernel", warm=True, steps=8)
    s_g, t_g = run("gather", warm=False, steps=1)
    assert t_m == t_g, "fused guided must match the host FSM path"
    assert s_m.flight.fused_sampled_windows_total > 0  # guided rides sampled epilogue
    assert s_m.flight.compiles_after_warmup_total == 0, (
        f"post-warmup compiles: {s_m.flight.post_warmup_keys}"
    )


# ---------------------------------------------------------------------------
# Fused speculative window
# ---------------------------------------------------------------------------


def _cache_rows(cache, tables, upto):
    """Gather per-position KV rows [B, upto, KVH*HD] (layer-stacked) from a
    paged cache given each row's block table and confirmed length."""
    L, N, BS = cache.shape[0], cache.shape[1], cache.shape[2]
    out = []
    for b in range(tables.shape[0]):
        rows = []
        for p in range(upto[b]):
            blk = int(tables[b, p // BS])
            rows.append(np.asarray(cache[:, blk, p % BS]))
        out.append(np.stack(rows, axis=1))  # [L, upto, KVH*HD]
    return out


@pytest.mark.slow  # interpret-mode Pallas e2e; the CI `fused-sampling`
# job gates the same invariants through bench.py in its own budget
def test_fused_spec_window_mixed_accept_kv_parity():
    """One fused spec launch (draft != target => real rejections): the
    host-replay contract reconstructs the confirmed token stream, SOME
    rounds accept and SOME reject (mixed coverage), and the target cache's
    confirmed KV rows are bit-for-bit what a clean prefill of that exact
    stream writes — i.e. rejection costs no rewind and leaves no stale
    confirmed state."""
    R, gamma, B, P = 3, 2, 2, 12
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    # Draft = target + tiny perturbation: a random-init tiny model's
    # argmax is noise-sensitive, so 0.002 is already enough for rows to
    # disagree — some proposals accept, some reject (both asserted).
    noise = jax.random.PRNGKey(42)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(noise, len(leaves))
    draft = jax.tree_util.tree_unflatten(
        treedef,
        [l + 0.002 * jax.random.normal(k, l.shape, l.dtype)
         for l, k in zip(leaves, keys)],
    )

    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 255, size=P) for _ in range(B)]
    tables = np.stack(
        [np.arange(1 + 2 * b, 3 + 2 * b, dtype=np.int32) for b in range(B)]
    )
    t_j = jnp.asarray(tables)

    k_t, v_t = _fresh(CFG, num_blocks=2 * B + 2)
    k_d, v_d = _fresh(CFG, num_blocks=2 * B + 2)
    for b in range(B):
        _, k_t, v_t = _prefill(params, CFG, k_t, v_t, prompts[b], t_j[b])
        _, k_d, v_d = _prefill(draft, CFG, k_d, v_d, prompts[b], t_j[b])

    t0 = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    xprev = jnp.asarray([int(p[-1]) for p in prompts], jnp.int32)
    pos = jnp.full((B,), P, jnp.int32)
    active = jnp.ones((B,), bool)
    greedy = (jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
              jnp.ones((B,), jnp.float32))
    unif = jnp.full((R, B, 2 * gamma + 1), 0.25, jnp.float32)

    n0 = mk.trace_launch_count()
    toks_out, accepted, k_t, v_t, k_d, v_d = llama.decode_spec_fused(
        params, MEGA, draft, MEGA, k_t, v_t, k_d, v_d,
        t0, xprev, pos, t_j, t_j, active, *greedy, unif,
        rounds=R, gamma=gamma,
    )
    assert mk.trace_launch_count() - n0 == 1, "spec window must be ONE launch"

    acc = np.asarray(accepted)  # [R, B]
    toks_h = np.asarray(toks_out)  # [R, B, gamma+1]
    assert acc.min() >= 0 and acc.max() <= gamma
    assert acc.max() > 0, "perturbed draft should still land some proposals"
    assert acc.min() < gamma, "perturbed draft should also get rejected"

    # Host-replay contract: per round, k accepted proposals then the
    # verifier's bonus/fallback token; cursor advances k+1.
    streams, upto = [], []
    for b in range(B):
        conf = list(prompts[b]) + [int(t0[b])]
        for r in range(R):
            kk = int(acc[r, b])
            conf += [int(t) for t in toks_h[r, b, :kk]] + [int(toks_h[r, b, gamma])]
        streams.append(conf)
        upto.append(len(conf) - 1)  # last token's KV is the next input, unwritten

    # Gold: clean prefill of each confirmed stream (same math, no spec).
    k_g, v_g = _fresh(CFG, num_blocks=2 * B + 2)
    for b in range(B):
        _, k_g, v_g = _prefill(params, CFG, k_g, v_g, streams[b][:-1], t_j[b])

    got_k = _cache_rows(k_t, tables, upto)
    got_v = _cache_rows(v_t, tables, upto)
    want_k = _cache_rows(k_g, tables, upto)
    want_v = _cache_rows(v_g, tables, upto)
    for b in range(B):
        np.testing.assert_allclose(got_k[b], want_k[b], atol=2e-4)
        np.testing.assert_allclose(got_v[b], want_v[b], atol=2e-4)


@pytest.mark.slow  # interpret-mode Pallas e2e; the CI `fused-sampling`
# job gates the same invariants through bench.py in its own budget
def test_scheduler_spec_fused_e2e():
    """Scheduler spec path rides the fused spec window (draft attached,
    gate engaged): greedy token parity with a plain gather scheduler, the
    spec-fused counters advance, >= 2 accepted tokens/round on the
    draft==target smoke config, and zero post-warmup compiles across the
    enlarged key space (fused greedy + sampled + spec executables warmed)."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

    def run(impl, draft, warm, steps):
        sched = Scheduler(CFG.replace(attention_impl=impl), params, SchedulerConfig(
            num_blocks=128, max_running=4,
            prefill_buckets=[32], decode_buckets=[1, 2, 4],
            num_scheduler_steps=steps, enable_prefix_caching=False,
            enable_overlap_decode=False, enable_mixed_batching=False,
        ), dtype=jnp.float32)
        if draft:
            sched.attach_draft(CFG, params, gamma=2)
        if warm:
            sched.warmup(ctx_tokens=64)
            sched.flight.mark_warmup_done(warmed=True)
        toks = {}
        for i in range(3):
            sched.add_request(f"s{i}", list(range(1 + i, 25 + i)),
                              SamplingParams(temperature=0.0),
                              StopConditions(max_tokens=12, ignore_eos=True))
        for _ in range(300):
            if not sched.has_work():
                break
            for s, o in sched.step():
                if o.token_id >= 0:
                    toks.setdefault(s.request_id, []).append(o.token_id)
        return sched, toks

    s_f, t_f = run("megakernel", draft=True, warm=True, steps=8)
    assert s_f._use_fused_spec, "fused spec gate must engage on the tiny config"
    s_g, t_g = run("gather", draft=False, warm=False, steps=1)
    assert t_f == t_g, "fused spec must emit identical greedy tokens"
    assert s_f.flight.spec_fused_windows_total > 0
    assert s_f.flight.spec_fused_accepted_tokens_total > 0
    assert s_f.flight.compiles_after_warmup_total == 0, (
        f"post-warmup compiles: {s_f.flight.post_warmup_keys}"
    )
    st = s_f.spec_stats.to_dict()
    assert st["accepted_per_round"] >= 2.0, st
    stats = s_f.flight.to_stats()
    assert stats["spec_fused_windows_total"] == s_f.flight.spec_fused_windows_total
