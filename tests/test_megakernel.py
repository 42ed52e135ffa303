"""Ragged paged-attention megakernel: interpreter-mode parity vs the XLA
gather path over head layouts (GQA/MQA/MHA), ragged edge cases (length-1
decode rows mixed with chunk rows, short sequences in wide buckets, page-
boundary prefix lengths, dead scratch-block-0 slots) and the int8-KV
dequant-in-VMEM path.

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
    # A decode_multi window streams params once per step; a single step
    # streams them once.
    _, b_loop = paged.step_cost(32, 800, param_passes=8.0)
    _, b_once = paged.step_cost(32, 100, param_passes=1.0)
    assert b_loop - b_once == pytest.approx(7 * 2000 + 700 * 10.0)


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
