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
# A chunk's queries share their grid steps: the tile's edges, against a dense
# float32 reference
# ---------------------------------------------------------------------------


def _dense_reference(q, ke, ve, k_pages, v_pages, tables, meta, kvh):
    """Plain float32 softmax attention, a query at a time, over ``[the first
    prefix_len tokens of the row's pages ; fresh keys [start, end)]``; dead
    queries return zeros."""
    q, ke, ve, k_pages, v_pages = (np.asarray(x, np.float32) for x in (q, ke, ve, k_pages, v_pages))
    NQ, H, HD = q.shape
    out = np.zeros_like(q)
    for n in range(NQ):
        row, prefix, start, end, live = (int(x) for x in np.asarray(meta)[:, n])
        if not live:
            continue
        pages = np.asarray(tables)[row]
        k = np.concatenate([k_pages[pages].reshape(-1, kvh, HD)[:prefix], ke[start:end]])
        v = np.concatenate([v_pages[pages].reshape(-1, kvh, HD)[:prefix], ve[start:end]])
        for h in range(H):
            s = k[:, h // (H // kvh)] @ q[n, h] * HD**-0.5
            p = np.exp(s - s.max())
            out[n, h] = (p / p.sum()) @ v[:, h // (H // kvh)]
    return out


# (queries in the chunk's bucket, live ones, prefix tokens, tile, KV heads, G, HD, int8 pool); W 4 pages of 16.
TILE_EDGES = {
    "chunk-shorter-than-a-tile": (6, 6, 21, 16, 2, 2, 16, False),
    "chunk-not-a-multiple": (19, 19, 21, 8, 2, 2, 16, False),
    "valid-under-bucket-with-a-dead-tile": (32, 13, 37, 8, 2, 2, 16, False),
    "one-live-query": (32, 1, 16, 8, 2, 2, 16, False),
    "prefix-0": (32, 32, 0, 16, 2, 2, 16, False),
    "prefix-on-a-page-boundary": (16, 16, 32, 8, 2, 2, 16, False),
    "prefix-in-the-tables-last-slot": (16, 16, 59, 8, 2, 2, 16, False),
    "prefix-fills-the-table": (16, 16, 64, 8, 2, 2, 16, False),
    "g1-mha": (24, 20, 37, 8, 4, 1, 16, False),
    "g4": (24, 20, 37, 8, 2, 4, 16, False),
    "mqa": (24, 20, 37, 8, 1, 4, 16, False),
    "hd128-a-group-a-head": (32, 29, 37, 16, 2, 4, 128, False),
    "hd64-two-heads-a-group": (24, 20, 50, 8, 4, 2, 64, False),
    "int8-pool": (24, 20, 37, 8, 2, 2, 16, True),
    "int8-pool-a-group-a-head": (16, 16, 21, 8, 2, 1, 128, True),
}


@pytest.mark.parametrize("edge", list(TILE_EDGES))
def test_tiled_chunk_matches_the_dense_reference(edge):
    """``tile`` queries of a chunk a grid row, and a query a grid row, give
    what plain softmax attention gives; dead queries return exact zeros."""
    from dynamo_tpu.engine.kv_cache import QuantKv, quantize_kv_rows

    nq, valid, prefix, tile, kvh, G, hd, quant = TILE_EDGES[edge]
    W, bs = 4, 16
    rng = np.random.default_rng(len(edge))
    q = jnp.asarray(rng.standard_normal((nq, kvh * G, hd)).astype(np.float32))
    ke = jnp.asarray(rng.standard_normal((nq, kvh, hd)).astype(np.float32))
    ve = jnp.asarray(rng.standard_normal((nq, kvh, hd)).astype(np.float32))
    k_pages = jnp.asarray(rng.standard_normal((W + 3, bs, kvh * hd)).astype(np.float32))
    v_pages = jnp.asarray(rng.standard_normal((W + 3, bs, kvh * hd)).astype(np.float32))
    k_ref, v_ref = k_pages, v_pages
    if quant:
        k_pages = quantize_kv_rows(k_pages.reshape(W + 3, bs, kvh, hd))
        v_pages = quantize_kv_rows(v_pages.reshape(W + 3, bs, kvh, hd))
        k_ref, v_ref = (p.q.astype(jnp.float32) * jnp.repeat(p.scale, hd, axis=-1) for p in (k_pages, v_pages))
        assert isinstance(k_pages, QuantKv)
    tables = jnp.asarray(np.array([[3, 1, 4, 2]], np.int32))
    i = jnp.arange(nq, dtype=jnp.int32)
    meta = mk.build_meta(jnp.zeros_like(i), jnp.full_like(i, prefix), jnp.zeros_like(i), i + 1, i < valid)
    want = _dense_reference(q, ke, ve, k_ref, v_ref, tables, meta, kvh)
    for t in (tile, 1):
        got = np.asarray(mk.ragged_paged_attention(
            q, ke, ve, k_pages, v_pages, tables, meta, num_kv_heads=kvh, block_size=bs, tile=t, interpret=True,
        ))
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=f"tile {t}")
        assert np.all(got[valid:] == 0.0), f"tile {t}: dead queries must return zeros"


@pytest.mark.parametrize("num_queries,heads,kv_heads,head_dim,want", [
    (256, 32, 8, 128, mk.TILE_MAX), (16, 4, 2, 16, 16), (19, 4, 2, 16, 32), (4, 4, 2, 16, mk.TILE_MIN),
    (2048, 32, 8, 128, 128), (256, 32, 32, 128, mk.TILE_MAX), (256, 64, 8, 128, 128),
])
def test_chunk_tile_follows_the_shapes(num_queries, heads, kv_heads, head_dim, want):
    """The tile is read off the chunk and the widths: a power of two that the
    chunk fills, capped where the chip read fastest and by the VMEM a launch
    may ask for (a chunk of 2,048 brings 2,048 fresh keys a score row;
    64 heads of 128 hold twice the state of 32)."""
    tile = mk.chunk_tile(num_queries, heads, kv_heads, head_dim, 128)
    assert tile == want
    assert mk._tile_vmem_bytes(tile, num_queries, heads, kv_heads, head_dim, 128, 2, 2) <= max(
        mk.TILE_VMEM, mk._tile_vmem_bytes(mk.TILE_MIN, num_queries, heads, kv_heads, head_dim, 128, 2, 2))
    assert mk.lane_fold(kv_heads, head_dim) * head_dim >= min(128, kv_heads * head_dim)


# ---------------------------------------------------------------------------
# The step programs: chunks through the tiles against the XLA gather path
# ---------------------------------------------------------------------------


@pytest.fixture(params=[None, 16], ids=["one-tile", "tiles-of-16"])
def tile_max(request, monkeypatch):
    """The program's tile for these chunks (one covers each), and tiles of 16:
    a chunk of 19 or 40 is then not a multiple, one of 9 in a bucket of 32
    leaves a tile wholly dead."""
    if request.param:
        monkeypatch.setattr(mk, "TILE_MAX", request.param)
    return request.param


@pytest.mark.parametrize("first_len,second_len", [(32, 19), (21, 40)], ids=["prefix-2-pages", "prefix-21"])
def test_prefill_chunk_with_prefix_parity(tile_max, first_len, second_len):
    """A (start, len) chunk row over a cached prefix — a chunk that starts
    exactly ON a page boundary, and one that starts inside a page — matches
    the gather path."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(2)
    table = jnp.asarray(np.arange(1, 6, dtype=np.int32))
    first = rng.integers(1, 255, size=first_len)  # 32 ends exactly at 2 pages (bs=16)
    second = rng.integers(1, 255, size=second_len)

    def run(cfg):
        k, v = _fresh(cfg)
        lg1, k, v = _prefill(params, cfg, k, v, first, table)
        lg2, k, v = _prefill(params, cfg, k, v, second, table, cache_len=first_len)
        return lg1, lg2, k, v

    g1, g2, kg, vg = run(CFG)
    m1, m2, km, vm = run(MEGA)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(m1), atol=2e-4)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(m2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(kg), np.asarray(km), atol=2e-5)


@pytest.mark.parametrize("bucket,valid", [(16, 9), (32, 9), (32, 27)], ids=["9-of-16", "9-of-32", "27-of-32"])
def test_mixed_step_parity_chunk_plus_decode_rows(tile_max, bucket, valid):
    """The whole mixed step — a ragged chunk row AND length-1 decode rows —
    matches the two-shape XLA path, including padded chunk
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

    chunk = np.zeros((bucket,), np.int32)
    chunk[:valid] = rng.integers(1, 255, size=valid)

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
                p, cfg, k, v, jnp.asarray(chunk), jnp.int32(valid), jnp.int32(21),
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


@pytest.mark.parametrize("tile", [1, 2, 4])
def test_dead_queries_return_zeros(tile):
    """Dead ragged rows (meta active=0) read nothing and return exact zeros
    from the kernel — the pl.when skip, not masked softmax garbage — whether
    a grid row is one query or a tile of them: two rows of four queries, the
    second row's last three dead (half a tile of 2 and one whole, or most of
    a tile of 4), and a third row dead altogether."""
    kvh, hd, bs = 2, 16, 16
    H = 4
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((12, H, hd)).astype(np.float32))
    ke = jnp.asarray(rng.standard_normal((12, kvh, hd)).astype(np.float32))
    # Pages in the pool's layout: heads merged into lanes (KvCacheArrays).
    k_pages = jnp.asarray(rng.standard_normal((6, bs, kvh * hd)).astype(np.float32))
    v_pages = jnp.asarray(rng.standard_normal((6, bs, kvh * hd)).astype(np.float32))
    tables = jnp.asarray(np.array([[1, 2], [3, 4], [0, 0]], np.int32))
    i = np.arange(12, dtype=np.int32)
    live = np.array([1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0], np.int32)
    meta = mk.build_meta(
        jnp.asarray(i // 4), jnp.asarray(np.where(i < 8, 20, 0).astype(np.int32)),
        jnp.asarray(i // 4 * 4), jnp.asarray(i + 1), jnp.asarray(live),
    )
    out = np.asarray(mk.ragged_paged_attention(
        q, ke, ke, k_pages, v_pages, tables, meta,
        num_kv_heads=kvh, block_size=bs, tile=tile, interpret=True,
    ))
    assert np.all(out[live == 0] == 0.0), "dead query must return zeros"
    assert np.all(np.isfinite(out)) and np.all(np.abs(out[live == 1]).sum(axis=(1, 2)) > 0)


# ---------------------------------------------------------------------------
# Length-1 rows walk the list of their live pages
# ---------------------------------------------------------------------------

# (prefix tokens a row, live a row, fresh keys a row beside its own, int8 pool); a table 6 pages of 16 wide.
ROWS = {
    "dead-rows-and-short-rows-in-a-wide-table": ([37, 0, 5, 16, 0, 96, 64, 0], [1, 0, 1, 1, 0, 1, 1, 0], 0, False),
    "one-live-row": ([0, 0, 21, 0], [0, 0, 1, 0], 0, False),
    "every-row-live-and-full": ([96, 96, 96, 96], [1, 1, 1, 1], 0, False),
    "no-live-row": ([0, 0, 0, 0], [0, 0, 0, 0], 0, False),
    "a-live-row-with-no-prefix": ([0, 40, 0], [1, 1, 0], 0, False),
    "a-dead-row-that-holds-pages": ([33, 50, 70], [1, 0, 1], 0, False),
    "int8-pool": ([37, 0, 5, 96], [1, 0, 1, 1], 0, True),
    "a-windows-carry-rows": ([37, 0, 16, 81], [1, 0, 1, 1], 3, False),
    "a-windows-carry-rows-int8-pool": ([37, 0, 16, 81], [1, 0, 1, 1], 2, True),
}


@pytest.fixture
def step_pages(monkeypatch):
    """``step_pages(P, page_bytes)``: the rows' launch takes ``P`` pages of
    ``page_bytes`` a step where its table admits them — the one constant
    ``pages_per_step`` reads, moved; the launch's traces are keyed by shapes,
    so they are dropped around the move."""
    def move(P, page_bytes):
        monkeypatch.setattr(mk, "ROWS_STEP_BYTES", P * page_bytes)
        mk.ragged_paged_attention.clear_cache()

    yield move
    mk.ragged_paged_attention.clear_cache()


def _work_reference(prefixes, live, W, bs, P=1):
    """``build_work`` in plain Python. At ``P`` 1 it is the list before PR
    47: an item ``row << 16 | slot`` a page under a live row's prefix."""
    items = []
    for r, (prefix, alive) in enumerate(zip(prefixes, live)):
        if alive:
            pages = max(min(-(-int(prefix) // bs), W), 1)
            items += [r << 16 | slot for slot in range(0, pages, P)]
    listed = np.zeros(mk.work_len(len(prefixes), W, P), np.int32)
    listed[0] = max(len(items), 1)
    listed[1 : 1 + len(items)] = items
    return listed


def _check_listed_rows(prefixes, live, window, quant, W, P, seed, walk=True):
    """The rows' launch over a table ``W`` pages of 16 wide, at ``P`` pages a
    step, against ``_dense_reference`` and (``walk``) the walk of every group
    of every bucket row; returns the list."""
    from dynamo_tpu.engine.kv_cache import quantize_kv_rows
    from tools.attn_chunk_bench import walk_work

    B, bs, kvh, G, hd = len(prefixes), 16, 2, 2, 16
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, kvh * G, hd)).astype(np.float32))
    ke = jnp.asarray(rng.standard_normal((B * (window + 1), kvh, hd)).astype(np.float32))
    ve = jnp.asarray(rng.standard_normal((B * (window + 1), kvh, hd)).astype(np.float32))
    n_pages = 1 + B * W
    k_pages = jnp.asarray(rng.standard_normal((n_pages, bs, kvh * hd)).astype(np.float32))
    v_pages = jnp.asarray(rng.standard_normal((n_pages, bs, kvh * hd)).astype(np.float32))
    k_ref, v_ref = k_pages, v_pages
    if quant:
        k_pages = quantize_kv_rows(k_pages.reshape(n_pages, bs, kvh, hd))
        v_pages = quantize_kv_rows(v_pages.reshape(n_pages, bs, kvh, hd))
        k_ref, v_ref = (p.q.astype(jnp.float32) * jnp.repeat(p.scale, hd, axis=-1) for p in (k_pages, v_pages))
    assert mk.pages_per_step(bs, kvh * hd, 1 if quant else 4, W) == P
    # A row's pages, in an order of their own; slots past them hold the scratch page, as the scheduler's tables do.
    held = -(-np.asarray(prefixes) // bs)
    tables = np.zeros((B, W), np.int32)
    for r in range(B):
        tables[r, : held[r]] = 1 + r * W + rng.permutation(W)[: held[r]]
    tables = jnp.asarray(tables)
    i = jnp.arange(B, dtype=jnp.int32)
    first = i * (window + 1)
    work = None
    for step in range(window + 1):
        meta = mk.build_meta(i, jnp.asarray(prefixes, jnp.int32), first, first + 1 + step, jnp.asarray(live, jnp.int32))
        if work is None:
            work = mk.build_work(meta[1], meta[4] > 0, W, bs, P)
            assert int(work[0]) == max(sum(-(-max(h, 1) // P) for h, l in zip(held, live) if l), 1)
            assert work.shape == (1 + B * (-(-W // P) + 1),)
        kw = dict(num_kv_heads=kvh, block_size=bs, interpret=True)
        got = np.asarray(mk.ragged_paged_attention(q, ke, ve, k_pages, v_pages, tables, meta, work, **kw))
        want = _dense_reference(q, ke, ve, k_ref, v_ref, tables, meta, kvh)
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=f"step {step}")
        dead = np.asarray(live) == 0
        assert np.all(got[dead] == 0.0), "dead rows must return zeros"
        if walk:
            every_row = mk.build_meta(*meta[:4], jnp.ones((B,), jnp.int32))
            walked = np.asarray(mk.ragged_paged_attention(q, ke, ve, k_pages, v_pages, tables, every_row, walk_work(B, W, P), **kw))
            assert np.array_equal(got[~dead], walked[~dead]), f"step {step}: the same pages in the same order, the same bits"
            built_here = np.asarray(mk.ragged_paged_attention(q, ke, ve, k_pages, v_pages, tables, meta, **kw))
            assert np.array_equal(got, built_here)
    return np.asarray(work)


@pytest.mark.parametrize("pages_per_step", [1, 2, 4])
@pytest.mark.parametrize("case", list(ROWS))
def test_listed_rows_match_the_dense_reference_and_the_walk_of_every_slot(case, pages_per_step, step_pages):
    """A batch of length-1 rows, its launch one step a group of
    ``pages_per_step`` live pages, the last of a row's closing it
    (``build_work``), gives what plain softmax attention gives, and on its
    live rows, to the bit, what the walk of every group of every bucket row
    gives (at one page a step the static grid before PR 38, every bucket row
    marked live as its callers did); dead rows return exact zeros. A window's
    steps (a row's fresh keys ``[start, start + 1 + step)``) share one list."""
    prefixes, live, window, quant = ROWS[case]
    step_pages(pages_per_step, 16 * 32 * (1 if quant else 4))
    _check_listed_rows(prefixes, live, window, quant, 6, pages_per_step, seed=len(case))


# Ragged prefixes at every edge of a group of P pages of 16, by the table's width W: no prefix, one token, a
# page less a token, exactly P pages, P pages and a token, the table's full width less a token and in full,
# dead rows (one that holds pages) between live ones.
def _group_edges(P, W):
    edges = [0, 1, 15, 16 * min(P, W), min(16 * P + 1, 16 * W), 0, 16 * W - 1, 40, 16 * W]
    return edges, [1, 1, 1, 1, 1, 0, 1, 0, 1]


@pytest.mark.parametrize("quant", [False, True], ids=["f32-pool", "int8-pool"])
@pytest.mark.parametrize("W", [3, 6, 9], ids=["table-3", "table-6", "table-9"])
@pytest.mark.parametrize("pages_per_step", [1, 2, 4])
def test_a_step_of_several_pages_at_every_edge_of_a_group(pages_per_step, W, quant, step_pages):
    """The rows' launch at 1, 2 and 4 pages a step over tables narrower than a
    group (3 slots under 4 pages: the step takes 2), not a multiple of it and
    wider, a window's two steps sharing one list, over float and int8 pages:
    plain softmax attention on every live row, zeros on the dead, and the list
    ``build_work`` is specified to build, element for element."""
    step_pages(pages_per_step, 16 * 32 * (1 if quant else 4))
    P = min(pages_per_step, 2 if W == 3 else W)
    prefixes, live = _group_edges(P, W)
    work = _check_listed_rows(prefixes, live, 1, quant, W, P, seed=P * W, walk=False)
    assert np.array_equal(work, _work_reference(prefixes, live, W, 16, P))


@pytest.mark.parametrize("pages_per_step", [1, 2, 4])
@pytest.mark.parametrize("case", list(ROWS))
def test_build_work_lists_a_group_of_pages_an_item(case, pages_per_step):
    """``build_work``: at one page a step the list before PR 47, element for
    element; at ``P`` an item every ``P`` slots of a live row's pages, the
    count ``sum ceil(max(pages, 1) / P)`` over live rows (1 with none)."""
    prefixes, live, _, _ = ROWS[case]
    W, bs = 6, 16
    args = (jnp.asarray(prefixes, jnp.int32), jnp.asarray(live, jnp.int32) > 0, W, bs)
    got = np.asarray(mk.build_work(*args, pages_per_step))
    assert np.array_equal(got, _work_reference(prefixes, live, W, bs, pages_per_step))
    held = [max(min(-(-p // bs), W), 1) for p, l in zip(prefixes, live) if l]
    assert got[0] == max(sum(-(-h // pages_per_step) for h in held), 1)
    if pages_per_step == 1:
        assert np.array_equal(got, np.asarray(mk.build_work(*args))) and got.shape == (1 + len(prefixes) * (W + 1),)


@pytest.mark.parametrize(
    "block_size,kv_lanes,kv_bytes,num_slots,want",
    [(128, 256, 2, 24, 4), (128, 1024, 2, 16, 1), (128, 4096, 2, 16, 1), (128, 256, 2, 3, 2), (128, 256, 2, 1, 1),
     (128, 256, 1, 24, 8), (128, 1024, 1, 16, 2), (128, 128, 2, 64, 8), (16, 512, 2, 64, 8), (16, 32, 4, 6, 4),
     (128, 256, 2, 4, 4), (128, 384, 2, 24, 2)],
    ids=["zaya", "llama-cells", "evabyte", "table-of-3", "table-of-1", "zaya-int8", "llama-int8", "one-head-of-128",
         "1b-pages-of-16", "tiny", "table-of-4", "three-heads"],
)
def test_pages_per_step_follows_the_pages_bytes(block_size, kv_lanes, kv_bytes, num_slots, want):
    """Pages whose bytes a side stay within a llama cell's one page (256 KB),
    a power of two, never more than the table holds or than the tool read."""
    got = mk.pages_per_step(block_size, kv_lanes, kv_bytes, num_slots)
    assert got == want and got <= num_slots and got & (got - 1) == 0
    assert got == 1 or got * block_size * kv_lanes * kv_bytes <= mk.ROWS_STEP_BYTES
    assert mk.work_len(5, num_slots, got) == 1 + 5 * (-(-num_slots // got) + 1)


def test_a_list_built_at_another_pages_per_step_fails_the_launchs_trace(step_pages):
    """The list and the launch read ``P`` off the same shapes through one
    function; a list built at another ``P`` has another length, and the launch
    refuses it while it traces."""
    B, W, bs, kvh, hd = 4, 6, 16, 2, 16
    step_pages(4, bs * kvh * hd * 4)
    i = jnp.arange(B, dtype=jnp.int32)
    meta = mk.build_meta(i, jnp.full((B,), 40, jnp.int32), i, i + 1, jnp.ones((B,), jnp.int32))
    z = jnp.zeros
    args = (z((B, kvh * 2, hd)), z((B, kvh, hd)), z((B, kvh, hd)), z((1 + B * W, bs, kvh * hd)), z((1 + B * W, bs, kvh * hd)),
            z((B, W), jnp.int32), meta)
    kw = dict(num_kv_heads=kvh, block_size=bs, interpret=True)
    assert {mk.work_len(B, W, P) for P in (1, 2, 4)} == {29, 17, 13}
    for P in (1, 2):
        with pytest.raises(AssertionError, match="work list"):
            mk.ragged_paged_attention(*args, mk.build_work(meta[1], meta[4] > 0, W, bs, P), **kw)
    mk.ragged_paged_attention(*args, mk.build_work(meta[1], meta[4] > 0, W, bs, 4), **kw)


@pytest.mark.parametrize("program", ["decode", "decode_multi"])
def test_a_padded_row_is_no_item_and_leaves_the_live_rows_as_they_were(program):
    """``active`` false on a bucket's padded row: the row is no step of any
    layer's launch, and the live rows' logits (``decode``) and tokens and
    per-step logits (a ``decode_multi`` window) are, to the bit, what they
    are with the padded row marked live, as every bucket row was before PR
    38; they match the gather path's."""
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    B, W = 4, 8
    prompts = [(rng.integers(1, 255, size=30), np.arange(1, 5)), (rng.integers(1, 255, size=7), np.arange(9, 13)),
               (rng.integers(1, 255, size=16), np.arange(13, 17))]
    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    pos = jnp.asarray(np.array([30, 7, 16, 0], np.int32))
    tables = np.zeros((B, W), np.int32)
    for r, (_, tbl) in enumerate(prompts):
        tables[r, : len(tbl)] = tbl
    tables = jnp.asarray(tables)
    f32, i32 = jnp.float32, jnp.int32

    def run(cfg, active):
        k, v = _fresh(cfg)
        for toks, tbl in prompts:
            _, k, v = _prefill(params, cfg, k, v, toks, jnp.asarray(np.r_[tbl, np.zeros(W - len(tbl))].astype(np.int32)))
        active = jnp.asarray(np.array(active))
        if program == "decode":
            return (jax.jit(lambda p, k, v: llama.decode(p, cfg, k, v, dtoks, pos, tables, active))(params, k, v)[0],)
        return jax.jit(lambda p, k, v: llama.decode_multi(
            p, cfg, k, v, dtoks, pos, tables, active, jnp.zeros((B,), f32), jnp.zeros((B,), i32), jnp.ones((B,), f32),
            jax.random.PRNGKey(1), 4, return_logits=True))(params, k, v)[:2]

    padded, every_row, gather = run(MEGA, [1, 1, 1, 0]), run(MEGA, [1, 1, 1, 1]), run(CFG, [1, 1, 1, 0])
    for a, b, g in zip(padded, every_row, gather):
        a, b, g = (np.asarray(x)[..., :3, :] if np.ndim(x) == 3 or program == "decode" else np.asarray(x)[:, :3] for x in (a, b, g))
        assert np.array_equal(a, b)
        if a.dtype == np.int32:
            assert np.array_equal(a, g)
        else:
            np.testing.assert_allclose(a, g, atol=2e-4)


# ---------------------------------------------------------------------------
# int8 KV: dequant-in-VMEM path
# ---------------------------------------------------------------------------


def test_int8_kv_megakernel_parity(tile_max):
    """Megakernel attention over a QuantKv cache (int8 codes + per-(token,
    head) scales dequantized in VMEM) matches the gather path reading the
    SAME quantized cache — bitwise-equal inputs, so tolerance is float
    accumulation, not quantization error. A fresh prefill, a chunk of 21
    over that int8 prefix (the tile dequantises a page once for its
    queries), then a decode step."""
    cfg8_g = CFG.replace(kv_cache_dtype="int8")
    cfg8_m = cfg8_g.replace(attention_impl="megakernel")
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    rng = np.random.default_rng(5)
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32))
    toks = rng.integers(1, 255, size=30)
    more = rng.integers(1, 255, size=21)

    B = 2
    dtoks = jnp.asarray(rng.integers(1, 255, size=B).astype(np.int32))
    pos = jnp.full((B,), 51, jnp.int32)
    tables_d = jnp.asarray(np.tile(np.arange(1, 5, dtype=np.int32), (B, 1)))
    active = jnp.ones((B,), bool)

    def run(cfg):
        k, v = _fresh(cfg)
        _, k, v = _prefill(params, cfg, k, v, toks, table)
        lg_chunk, k, v = _prefill(params, cfg, k, v, more, table, cache_len=30)
        lg, k, v = jax.jit(
            lambda p, k, v: llama.decode(p, cfg, k, v, dtoks, pos, tables_d, active)
        )(params, k, v)
        return lg_chunk, lg

    for g, m in zip(run(cfg8_g), run(cfg8_m)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(m), atol=5e-4)


# ---------------------------------------------------------------------------
# The mechanism, read off the traced programs: grid steps of the launches
# ---------------------------------------------------------------------------


def _kernel_grids(jaxpr):
    """Grids of the ``pallas_call``s under ``jaxpr``, a layer scan's once."""
    from tests.test_kv_layout import _sub_jaxprs

    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for inner in _sub_jaxprs(eqn):
            grids += _kernel_grids(inner)
    return grids


# The cells' widths (Mistral-7B: 32/8 heads of 128, pages of 128) at two layers, traced on shapes alone.
CELL = CFG.replace(name="cell-widths", hidden_size=4096, num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=512,
                   block_size=128, max_seq_len=2048, attention_impl="megakernel")


@pytest.mark.parametrize("cfg,S,B,W", [(CELL, 256, 32, 12), (CELL, 256, 4, 16), (MEGA, 32, 4, 4), (MEGA, 16, 2, 8)],
                         ids=["cell-b32", "cell-b4", "tiny-32", "tiny-16"])
def test_a_chunks_queries_share_their_grid_steps(cfg, S, B, W):
    """A chunk of ``S`` queries takes ``ceil(S/TQ)*(W+1)`` grid steps a layer
    in ``mixed_step`` and in ``prefill`` — not ``S*(W+1)``, a table walk a
    query (3,744 at the cell's 288 x 13 before PR 31) — and the ``B`` decode
    rows beside it one axis of steps whose bound is traced: the count of their
    live items (``build_work``), not ``B*(W+1)``."""
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16))
    cache = jax.ShapeDtypeStruct((cfg.num_layers, 64, cfg.block_size, cfg.kv_size), jnp.bfloat16)
    i32 = jnp.int32
    tq = mk.chunk_tile(S, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.block_size)
    tiles = -(-S // tq)
    assert llama.chunk_attn_path(cfg, cache, S, jnp.bfloat16) == f"tile{tq}"

    mixed = jax.make_jaxpr(lambda p, k, v: llama.mixed_step(
        p, cfg, k, v, jnp.zeros((S,), i32), i32(S - 3), i32(40), jnp.ones((W,), i32),
        jnp.zeros((B,), i32), jnp.full((B,), 9, i32), jnp.ones((B, W), i32), jnp.ones((B,), bool)))(shapes, cache, cache)
    from jax._src.pallas.core import dynamic_grid_dim

    grids = _kernel_grids(mixed.jaxpr)
    assert sorted(grids, key=len) == [(dynamic_grid_dim,), (tiles, W + 1)]
    assert tiles * (W + 1) < S * (W + 1)

    chunk = jax.make_jaxpr(lambda p, k, v: llama.prefill(
        p, cfg, k, v, jnp.zeros((S,), i32), i32(S - 3), i32(40), jnp.ones((W,), i32)))(shapes, cache, cache)
    assert _kernel_grids(chunk.jaxpr) == [(tiles, W + 1)]


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
