"""Ragged paged-attention megakernel: one Pallas kernel for every row of a
step's attention, launched once for its length-1 rows and once for a wide row.

Why this exists: the r4 per-piece Pallas paged kernel issued 2+ launches
per layer (chunk flash kernel + decode prefix kernel), and the XLA gather
fallback moves triple traffic (gather read + packed-copy write + attend
re-read). (Blueprint: "Ragged Paged Attention", arxiv 2604.15464; the
upstream TPU kernel, ``jax.experimental.pallas.ops.tpu.ragged_paged_attention``,
for the tiling — its pool interleaves K and V and is not ours.)

``ragged_paged_attention``: a row is a ``(start, len)`` run of queries over
``[paged prefix ; fresh keys]``. Decode entries are length-1 rows and walk a
*work list* (``build_work``): one grid step a group of ``P`` consecutive
table slots of a live row whose first holds part of its prefix
(``pages_per_step``: as many pages as fill 256 KB a side, 1 from a page of
128 tokens by 1,024 bf16 lanes, 4 by 256 lanes — a step costs the serial
chain inside it, not its bytes, so narrow pages share one), its P key pages
and P value pages fetched together, scored under one frontier mask and
folded by ONE online-softmax update; the last step of a row also closes it
(its fresh keys, the normalisation, its output block), the count of steps
is the grid's traced bound — a padded row of the batch bucket, and a group
past a row's prefix, is no step at all; a prefill chunk is one wide row and walks the
static grid ``(tile of queries, page)`` — ``tile`` (``chunk_tile``)
consecutive queries share a grid row, so one page fetch, one score dot and
one value dot a page serve all of them, and the fresh keys are one step a
tile with the causal frontier taken from the tile's first query. One kernel
body, parameterised by the static tile and by where it reads its step from.
``decode`` and ``decode_multi`` launch it once a layer, ``prefill`` once
(tiled), ``mixed_step`` twice: the chunk's queries and the decode rows are
disjoint outputs, so nothing merges. With

- *scalar-prefetched block tables* (the page fetch is a plain BlockSpec
  whose index_map reads the table; Pallas double-buffers the HBM→VMEM
  streams, nothing is ever written back — vs the gather's 3× traffic),
- for a length-1 row the *block-diagonal GQA fold* proven in
  ``attention/decode.py`` (one MXU-shaped dot per page instead of G tiny
  ones; decode attention has ~100× MXU headroom, bytes are the budget); for a
  tile, which is not short of MXU rows, dots inside a *lane group* of whole
  KV heads (``lane_fold``: one head from a head size of 128) — no fold
  FLOPs, no ×KVH query bytes,
- no step for what is dead: a rows launch lists its live groups of pages
  only, so a ragged batch costs its pages, not bucket x table width (a slot
  of a row's last group past its prefix is a fetch that is masked: a step is
  bound by its chain, not its bytes, and 40 rows of 5 pages take what 40 of
  8 take at 4 pages a step, 102 us a layer; on a v5e an empty
  grid step is 0.14 µs of pipeline bookkeeping, and a padded row 0.6 µs more:
  its 64 KB query and output blocks, its state, its fresh-key dots); a
  chunk's padded queries, wholly padded tiles and table slots past its
  prefix are ``pl.when``-skipped steps of its short static grid (no compute,
  no page fetch beyond the scratch page),
- an int8-KV dequant-in-VMEM path (per-(token, head) scales streamed
  alongside the int8 codes and expanded over lanes in-kernel; a tile
  dequantises a page once for all its queries), so capacity-mode
  deployments keep the fused path.

On a v5e (tools/attn_chunk_bench.py, PERF.md §6 PR 31): 256 chunk queries
of 32/8 heads of 128 beside 32 decode rows of 12 pages took 3,576–4,034 µs a
layer as one (query, page) walk; the chunk at a tile of 256 takes 43–146 µs
(prefix 0–1,408) and the rows' launch 456. Folded over all KV heads a
tile of 32 took 340–650. The rows' launch (``--study rows``, PERF.md §6 PR
38; a window's last step, 9 fresh keys a row): 5 live rows of 4 pages in a
bucket of 32 took 94–110 µs as the walk of ``32 x (width + 1)`` steps and
take 37 as the list of their 20; a full batch (32 rows of 12 pages) 395 and
369.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# A wide row's tile (``chunk_tile``): at least a bf16 sublane tile of queries,
# at most what the chip read as fastest (tools/attn_chunk_bench.py, PERF.md
# §6 PR 31), inside the VMEM the launch may ask for.
TILE_MIN = 16
TILE_MAX = 256
TILE_VMEM = 40 << 20
TILE_VMEM_LIMIT = 64 << 20


def build_meta(
    row_of: jax.Array,  # [NQ] i32 — block-table row of each query
    prefix_len: jax.Array,  # [NQ] i32 — cached-prefix length each query attends
    extra_start: jax.Array,  # [NQ] i32 — first fresh-key column (incl.)
    extra_end: jax.Array,  # [NQ] i32 — fresh-key causal frontier (excl.)
    active: jax.Array,  # [NQ] bool/i32 — dead queries skip pages AND compute
) -> jax.Array:
    """Pack per-query ragged metadata into the kernel's [5, NQ] i32 table."""
    return jnp.stack(
        [
            row_of.astype(jnp.int32),
            prefix_len.astype(jnp.int32),
            extra_start.astype(jnp.int32),
            extra_end.astype(jnp.int32),
            active.astype(jnp.int32),
        ]
    )


# A work item: row << 16 | the first table slot of its group of pages.
_ROW_SHIFT = 16
_SLOT_MASK = (1 << _ROW_SHIFT) - 1

# What a step of a rows launch should fetch a side (``pages_per_step``): a
# page of 128 tokens by 1,024 bf16 lanes, the llama cells' page. A step costs
# the serial chain inside it (dots of 8 rows, mask, max, exp, three
# read-modify-writes of the softmax state, the pipeline's bookkeeping and its
# DMA waits), not its bytes. On a v5e (tools/attn_chunk_bench.py --study rows
# --heads 8 --lanes 256 --pages-per-step 1 2 4 8, PERF.md section 6 PR 47),
# pages of 128 tokens by 256 lanes, 64 KB a side: a step of 1 / 2 / 4 / 8
# pages takes 0.49 / 0.65 / 0.9 / 1.6 us, so 40 rows of 7 pages in a bucket
# of 64 take 168 / 131 / 106 / 97 us a layer (their 37 MB need 45) and 40 rows
# of 16 pages 343 / 229 / 179 / 162, but 40 rows of 3 pages 90 / 81 / 71 / 98:
# past 256 KB a side a short row pays for pages it masks.
ROWS_STEP_BYTES = 256 << 10
# No more page operands a side than the tool read: 2 P operands a launch, 4 P
# over an int8 pool, every one a DMA to start, to wait for and to compare with
# the last step's. Reached only by pages under 32 KB a side.
ROWS_STEP_PAGES = 8


def pages_per_step(block_size: int, kv_lanes: int, kv_bytes: int, num_slots: int) -> int:
    """Consecutive table slots that one step of a length-1 rows launch takes
    (``P``): the largest power of two of pages whose bytes a side stay within
    ``ROWS_STEP_BYTES``, at least 1, at most ``ROWS_STEP_PAGES`` and the
    table's width. 4 for pages of 128 tokens by 256 bf16 lanes, 1 by 1,024.
    ``kv_lanes`` are the launch's own (a tp shard's)."""
    most = min(ROWS_STEP_BYTES // (block_size * kv_lanes * kv_bytes), ROWS_STEP_PAGES, num_slots)
    p = 1
    while p * 2 <= most:
        p *= 2
    return p


def work_len(num_rows: int, num_slots: int, pages_per_step: int = 1) -> int:
    """Length of ``build_work``'s list: the count, then room for every group
    of every row and one more a row. It differs between any two ``P`` a table
    admits, so a list built at another ``P`` than its launch's fails the
    launch's trace."""
    return 1 + num_rows * (-(-num_slots // pages_per_step) + 1)


def build_work(
    prefix_len: jax.Array, active: jax.Array, num_slots: int, block_size: int, pages_per_step: int = 1,
) -> jax.Array:
    """The work list of a length-1 rows launch (``prefix_len`` ``[NQ]`` i32
    and ``active`` ``[NQ]`` bool as ``build_meta`` takes them), ``[work_len(NQ,
    W, P)]`` i32: the count of live items, then the items, a live row's
    after the live row before it — one a group of ``P`` (``pages_per_step``)
    consecutive table slots of which the first holds part of its prefix,
    first slot 0, P, 2 P, ... (slot 0 alone for a row with no prefix). The
    launch's grid is the count: a dead row, and a group past a row's prefix,
    is no step at all; a row's last item also closes it (its fresh keys, the
    normalisation, its output block). Prefixes and liveness are a step's, not
    a layer's: build it once a step program, outside the layer scan. With no
    live row the count is 1 and the one item is row 0's slot 0, which is dead
    and reads nothing."""
    NQ, W, P = prefix_len.shape[0], num_slots, pages_per_step
    G = -(-W // P)
    assert NQ < 1 << (31 - _ROW_SHIFT) and W < _SLOT_MASK and P >= 1, (NQ, W, P)
    # A dozen and a half primitives bound directly: an operator on a traced
    # array, an index or a jax.numpy function is a jitted helper traced anew
    # at every new shape, every primitive is lowered anew at every new shape,
    # and a warm set-up traces and lowers this once a step program (PERF.md
    # section 6, PR 38).
    cells, past = (NQ, G + 1), NQ * (G + 1)
    over = functools.partial(lax.broadcast_in_dim, shape=cells, broadcast_dimensions=(0,))

    def full(value, shape=(NQ,)):
        return np.full(shape, value, np.int32)

    if active.dtype != jnp.bool_:
        active = lax.ne(active, lax.full_like(active, 0))
    pages = lax.min(lax.div(lax.add(prefix_len, full(block_size - 1)), full(block_size)), full(W))
    if P > 1:
        pages = lax.div(lax.add(pages, full(P - 1)), full(P))
    counts = lax.select(active, lax.max(pages, full(1)), full(0))
    ends = lax.cumsum(counts)
    # Group j of row r for every (r, j) of the bucket: a row's first counts[r]
    # land at its start + j, the rest past the list's end, where they drop.
    j = np.broadcast_to(np.arange(G + 1, dtype=np.int32), cells)
    items = (np.arange(NQ, dtype=np.int32)[:, None] << _ROW_SHIFT) | j * P
    place = lax.select(lax.lt(j, over(counts)), lax.add(over(lax.sub(ends, counts)), j), full(past, cells))
    listed = lax.scatter(
        full(0, (past,)), lax.reshape(place, (past, 1)), items.reshape(past),
        lax.ScatterDimensionNumbers(update_window_dims=(), inserted_window_dims=(0,), scatter_dims_to_operand_dims=(0,)),
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )
    return lax.concatenate([lax.max(lax.slice(ends, (NQ - 1,), (NQ,)), full(1, (1,))), listed], 0)


def lane_fold(num_kv_heads: int, head_dim: int) -> int:
    """KV heads that a tile's dots fold into one lane group: the fewest whose
    lanes fill a vreg row (128), so a page is cut at lane-tile boundaries and
    the fold's extra FLOPs stop at 128 / HD. 1 from a head size of 128."""
    return max(
        d for d in range(1, num_kv_heads + 1)
        if num_kv_heads % d == 0 and d * head_dim <= max(128, head_dim)
    )


def _tile_vmem_bytes(tile, fresh, num_heads, num_kv_heads, head_dim, block_size, q_bytes, kv_bytes):
    """What a tiled launch asks of VMEM: the tile's operand and output
    (double-buffered), its f32 softmax state (``m`` and ``l`` pad to a lane
    tile), the ``fresh`` keys and the pages in flight, one dequantised page
    and one group's scores."""
    fold = lane_fold(num_kv_heads, head_dim)
    lanes = max(fold * head_dim, 128)
    rows = tile * num_heads
    kv_lanes = num_kv_heads * head_dim
    group_rows = tile * fold * (num_heads // num_kv_heads)
    return (
        2 * 2 * rows * lanes * q_bytes
        + rows * (lanes + 2 * 128) * 4
        + 2 * 2 * fresh * kv_lanes * q_bytes
        + 2 * 2 * block_size * kv_lanes * kv_bytes
        + 2 * block_size * kv_lanes * q_bytes
        + 3 * group_rows * max(block_size, fresh) * 4
    )


def chunk_tile(
    num_queries: int, num_heads: int, num_kv_heads: int, head_dim: int,
    block_size: int, q_bytes: int = 2, kv_bytes: int = 2,
) -> int:
    """Queries of one wide row (a prefill chunk) that share a grid row: the
    largest power of two up to ``TILE_MAX`` that the chunk fills and whose
    working set fits ``TILE_VMEM``. The heads are the caller's shard's."""
    tile = TILE_MIN
    while (
        tile < min(num_queries, TILE_MAX)
        and _tile_vmem_bytes(
            tile * 2, num_queries, num_heads, num_kv_heads, head_dim, block_size, q_bytes, kv_bytes
        ) <= TILE_VMEM
    ):
        tile *= 2
    return tile


def _online_update(m_ref, l_ref, acc_ref, rows, scores, values):
    """Fold score tiles (equal shapes) and their value tiles into ``rows`` of
    the online-softmax scratch, as one update: one running max over all of
    them, one rescale of the state."""
    m_prev = m_ref[rows]
    m_new = jnp.maximum(m_prev, jnp.max(functools.reduce(jnp.maximum, scores), axis=1, keepdims=True))
    ps = [jnp.exp(s - m_new) for s in scores]
    alpha = jnp.exp(m_prev - m_new)
    pv = functools.reduce(lax.add, [
        lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for p, v in zip(ps, values)
    ])
    m_ref[rows] = m_new
    l_ref[rows] = l_ref[rows] * alpha + jnp.sum(functools.reduce(lax.add, ps), axis=1, keepdims=True)
    acc_ref[rows] = acc_ref[rows] * alpha + pv


def _mega_kernel(
    tables_ref,  # SMEM [R, W] i32 — per-row page ids (layer-offset, dead → 0)
    meta_ref,  # SMEM [5, NT] i32 — build_meta layout, one column a grid row
    *refs,  # work_ref? wq_ref, ke_ref, ve_ref, k_refs, v_refs, (ks_refs, vs_refs)? o_ref, m_ref, l_ref, acc_ref
    block_size: int,
    num_slots: int,
    scale: float,
    quant: bool,
    tile: int,
    groups: int,
    pages_per_step: int,
):
    """A grid row is ``tile`` consecutive queries of one sequence row: one
    query (``tile`` 1, a decode row) or a run of a chunk's. Its queries share
    the row's prefix and every page fetch; the fresh-key frontier of query
    ``i`` of the tile is the first's plus ``i``, and the first
    ``meta[4]`` of them are live. The lanes of a page are cut into
    ``groups`` runs of whole KV heads; a group's ``rows`` queries-by-heads
    meet only its lanes (block-diagonally where it folds several heads).

    A step is one (grid row, table slot): read off the grid ``(NT, W + 1)``,
    whose last step a grid row, past its table, closes the row; or, for
    length-1 rows (``tile`` 1), off item ``program_id(0)`` of the work list (``build_work``:
    SMEM ``[work_len(NT, W, P)]``), whose grid is its live items and nothing
    else. A listed step takes ``P`` (``pages_per_step``) consecutive slots
    from its item's: P key pages and P value pages in flight, their scores
    masked by one frontier and folded by one softmax update; a row closes on
    the step of its last group."""
    listed, P = tile == 1, pages_per_step
    if listed:
        work_ref, *refs = refs
    # wq_ref VMEM [1, groups*rows, lanes]: this grid row's queries, folded;
    # ke_ref, ve_ref VMEM [CK, KVHD]: ALL fresh keys (lane-merged), loaded
    # once; k_refs, v_refs P x VMEM [1, BS, KVHD]: this step's pages.
    wq_ref, ke_ref, ve_ref, *rest = refs
    k_refs, v_refs, rest = rest[:P], rest[P : 2 * P], rest[2 * P :]
    if quant:
        ks_refs, vs_refs, rest = rest[:P], rest[P : 2 * P], rest[2 * P :]
    else:
        ks_refs = vs_refs = (None,) * P
    o_ref, m_ref, l_ref, acc_ref = rest
    if listed:
        item = work_ref[1 + pl.program_id(0)]
        nq, w = item >> _ROW_SHIFT, item & _SLOT_MASK
    else:
        nq, w = pl.program_id(0), pl.program_id(1)
    prefix_len = meta_ref[1, nq]
    e_start = meta_ref[2, nq]
    e_end = meta_ref[3, nq]
    n_live = meta_ref[4, nq]
    live = n_live > 0
    bs = block_size
    if listed:
        # A listed row closes on the step of its prefix's last group of pages (slot 0 where it has none),
        pages = lax.min(lax.div(prefix_len + (bs - 1), jnp.int32(bs)), jnp.int32(num_slots))
        if P > 1:
            pages = lax.div(pages + (P - 1), jnp.int32(P))
        last = lax.max(pages, jnp.int32(1)) - 1
        closes = w == (last * P if P > 1 else last)
    else:
        # a grid row on a step of its own after its table's last slot.
        closes = w == num_slots
    rows = wq_ref.shape[1] // groups
    lanes = wq_ref.shape[2]
    dtype = wq_ref.dtype
    # One group: its operand serves both pieces. Several: each loads its rows
    # where it meets its lanes, so no more than one group is held at a time.
    wq = wq_ref[0] if groups == 1 else None

    def page(ref, scale_ref, g):
        """Lane group ``g`` of a page in flight (``[1, BS, KVHD]``, int8
        with its scales) or of the fresh keys (``[CK, KVHD]``)."""
        ln = slice(g * lanes, (g + 1) * lanes)
        if scale_ref is None:
            return ref[0, :, ln] if len(ref.shape) == 3 else ref[:, ln]
        # int8 dequant in VMEM: per-(token, head) scales expand over
        # the HD lanes (lane j of the merged (kvh, hd) axis carries
        # head j // HD). The codes stream at 1 byte/value — the whole
        # point of int8 KV is capacity, and the fused path keeps it.
        hd = ref.shape[2] // scale_ref.shape[2]
        heads = slice(g * lanes // hd, (g + 1) * lanes // hd)
        return ref[0, :, ln].astype(dtype) * jnp.repeat(
            scale_ref[0, :, heads], hd, axis=-1
        ).astype(dtype)

    def attend(keys, key_scales, values, value_scales, mask):
        """Every group's scores against each of ``keys`` (equal shapes), piece
        ``i`` kept where ``mask(i, shape)`` says, folded with ``values`` into
        the scratch in one update a group."""
        keep = None
        for g in range(groups):
            r = slice(g * rows, (g + 1) * rows)
            ks = [page(*ref, g) for ref in zip(keys, key_scales)]  # [n, lanes] each
            vs = [page(*ref, g) for ref in zip(values, value_scales)]
            q = wq if groups == 1 else wq_ref[0, r, :]
            ss = [
                lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
                )
                * scale
                for k in ks
            ]  # [rows, n] each
            if keep is None:
                keep = [mask(i, s.shape) for i, s in enumerate(ss)]
            ss = [jnp.where(kept, s, NEG_INF) for kept, s in zip(keep, ss)]
            _online_update(m_ref, l_ref, acc_ref, r, ss, vs)

    @pl.when(w == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    # Paged-prefix piece: slot w + i holds tokens [(w+i)*bs, (w+i)*bs+bs) of
    # this grid row's sequence. On the static grid dead rows and slots past
    # the true prefix are skipped — no page fetch is wasted on the table's
    # width (consecutive identical table entries reuse the pipelined fetch,
    # so a short prefix in a wide table costs one scratch-page fetch, not W);
    # a list holds no such step, and a slot of a listed group past the prefix
    # is all masked.
    @pl.when(live & (w < num_slots) & (w * bs < prefix_len))
    def _page():
        def in_prefix(i, shape):
            kpos = (w + i if i else w) * bs + lax.broadcasted_iota(jnp.int32, shape, 1)
            return kpos < prefix_len

        attend(k_refs, ks_refs, v_refs, vs_refs, in_prefix)

    # Closing step: the in-flight (not-yet-cached) keys — a chunk query's
    # causal window over its own chunk, a decode query's current token, a
    # window query's carry rows — then close the softmax and normalize.
    @pl.when(closes)
    def _fresh_and_final():
        @pl.when(live & (e_end > e_start))
        def _fresh():
            def in_window(_, shape):
                cpos = lax.broadcasted_iota(jnp.int32, shape, 1)
                end = e_end
                if tile > 1:
                    end = e_end + lax.broadcasted_iota(jnp.int32, shape, 0) // (rows // tile)
                return (cpos >= e_start) & (cpos < end)

            attend((ke_ref,), (None,), (ve_ref,), (None,), in_window)

        out = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        if tile > 1:
            # A live tile's dead queries (a chunk's pad) attended as live
            # ones do; they return the zeros a dead grid row returns.
            qi = lax.broadcasted_iota(jnp.int32, (groups * rows, 1), 0) % rows // (rows // tile)
            out = jnp.where(qi < n_live, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_kv_heads", "block_size", "tile", "interpret")
)
def ragged_paged_attention(
    q: jax.Array,  # [NQ, H, HD] post-rope queries
    k_extra: jax.Array,  # [CK, KVH, HD] in-flight keys (chunk K, window rows, current tokens)
    v_extra: jax.Array,
    k_pages,  # [NP, BS, KVH*HD] layer-flat page pool, or QuantKv (scales [NP, BS, KVH])
    v_pages,
    tables: jax.Array,  # [R, W] i32 — per-sequence-row page ids (layer-offset)
    meta: jax.Array,  # [5, NQ] i32 — build_meta
    work: jax.Array | None = None,  # [work_len(NQ, W, P)] i32 — build_work of meta's prefixes and liveness at this launch's pages_per_step, length-1 rows only
    *,
    num_kv_heads: int,
    block_size: int,
    tile: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Attention for a ragged batch over [paged prefix ; fresh keys] in one
    kernel launch. Returns normalized ``[NQ, H, HD]`` — the prefix pages and
    the fresh piece merge inside the kernel's online softmax, so no external
    ``_merge_pieces`` is needed and no gathered prefix copy is ever
    materialized in HBM.

    ``tile`` > 1 (``chunk_tile``) is the caller's word that ``meta`` describes
    wide rows: every run of ``tile`` queries from a multiple of ``tile`` has
    one ``row_of``, ``prefix_len`` and ``extra_start``, ``extra_end`` rising
    by one a query, and its live queries first — a prefill chunk. The grid
    then walks (tile, page), not (query, page).

    Length-1 rows (``tile`` 1) walk their ``work`` list (``build_work``): a
    step a live page and one to close a live row, the count of them the
    grid's traced bound. A step program builds the list once and hands it to
    every layer's launch; left out, it is built here.

    Dead queries (``meta`` active = 0) return zeros and read nothing.

    The pages reach ``pallas_call`` untouched: the pool is stored in the
    layout the page ``BlockSpec`` reads (the contract: ``KvCacheArrays``).
    """
    from dynamo_tpu.engine.kv_cache import QuantKv

    NQ, H, HD = q.shape
    KVH = num_kv_heads
    G = H // KVH
    KVG, KVHD = KVH * G, KVH * HD
    W = tables.shape[1]
    CK = k_extra.shape[0]
    quant = isinstance(k_pages, QuantKv)

    if tile == 1:
        # Block-diagonal GQA fold (attention/decode.py): off-block lanes hit
        # zeros, so one [KVG, KVHD]×[KVHD, BS] dot yields exact per-head
        # scores. The ×KVH query-byte inflation is immaterial next to the KV
        # bytes the kernel exists to save.
        NT, fold = NQ, KVH
        folded = (NQ, KVH, G, KVH, HD)
        wq = lax.mul(
            lax.broadcast_in_dim(lax.reshape(q, (NQ, KVH, G, HD)), folded, (0, 1, 2, 4)),
            lax.broadcast_in_dim(np.eye(KVH, dtype=q.dtype), folded, (1, 3)),
        )
        wq = lax.reshape(wq, (NQ, KVG, KVHD))
    else:
        # A tile is not short of MXU rows: its dots stay inside a lane group
        # (one KV head from HD 128), rows (query, folded head, g).
        NT, fold = -(-NQ // tile), lane_fold(KVH, HD)
        pad = NT * tile - NQ
        wq = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(NT, tile, KVH // fold, fold, G, 1, HD)
        if fold > 1:
            wq = wq * jnp.eye(fold, dtype=q.dtype)[:, None, :, None]
        wq = wq.transpose(0, 2, 1, 3, 4, 5, 6).reshape(NT, tile * KVG, fold * HD)
        first = jnp.pad(meta, ((0, 0), (0, pad))).reshape(5, NT, tile)
        meta = jnp.concatenate([first[:4, :, 0], first[4:].sum(-1)])
    groups = KVH // fold
    block = (1,) + wq.shape[1:]

    ke = k_extra.reshape(CK, KVHD)
    ve = v_extra.reshape(CK, KVHD)

    BS = k_pages.shape[1]
    assert k_pages.shape[2] == KVHD, (k_pages.shape, KVH, HD)

    # Where a step is, (grid row, table slot): off the grid, or off its item.
    P = 1
    if tile == 1:
        P = pages_per_step(BS, KVHD, jnp.dtype((k_pages.q if quant else k_pages).dtype).itemsize, W)
        if work is None:
            work = build_work(meta[1], meta[4] > 0, W, block_size, P)
        assert work.shape == (work_len(NQ, W, P),), f"a work list of {work.shape} for {NQ} rows of {W} slots by {P}"
        scalars = (tables, meta, work)
        grid = (lax.index_in_dim(work, 0, keepdims=False),)

        def at(i, t, mt, wk):
            return wk[1 + i] >> _ROW_SHIFT, wk[1 + i] & _SLOT_MASK
    else:
        scalars = (tables, meta)
        grid = (NT, W + 1)

        def at(nq, w, t, mt):
            return nq, w

    def row_idx(*step):
        return (at(*step)[0], 0, 0)

    def page_idx(i, *step):
        """Slot ``w + i`` of the step's row (past a listed row's prefix the
        table holds the scratch page, and the kernel masks all of it)."""
        nq, w = at(*step)
        t, mt = step[len(grid) : len(grid) + 2]
        return (t[mt[0, nq], jnp.minimum(w + i if i else w, W - 1)], 0, 0)

    def fresh_idx(*_):
        return (0, 0)

    def page_specs(lanes):
        return [pl.BlockSpec((1, BS, lanes), functools.partial(page_idx, i)) for i in range(P)]

    in_specs = [
        pl.BlockSpec(block, row_idx),
        pl.BlockSpec((CK, KVHD), fresh_idx),
        pl.BlockSpec((CK, KVHD), fresh_idx),
        *page_specs(KVHD),
        *page_specs(KVHD),
    ]
    if quant:
        in_specs += page_specs(KVH) + page_specs(KVH)
        args = [wq, ke, ve, *[k_pages.q] * P, *[v_pages.q] * P, *[k_pages.scale] * P, *[v_pages.scale] * P]
    else:
        args = [wq, ke, ve, *[k_pages] * P, *[v_pages] * P]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, row_idx),
        scratch_shapes=[
            pltpu.VMEM((block[1], 1), jnp.float32),
            pltpu.VMEM((block[1], 1), jnp.float32),
            pltpu.VMEM(block[1:], jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _mega_kernel,
            block_size=block_size,
            num_slots=W,
            scale=HD**-0.5,
            quant=quant,
            tile=tile,
            groups=groups,
            pages_per_step=P,
        ),
        out_shape=jax.ShapeDtypeStruct(wq.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        # A tile's state is megabytes where a query's is kilobytes.
        compiler_params=None if tile == 1 else pltpu.CompilerParams(vmem_limit_bytes=TILE_VMEM_LIMIT),
    )(*(s.astype(jnp.int32) for s in scalars), *args)

    # Each query's output lives in its head's diagonal block of the fold.
    if tile == 1:
        diagonal = lax.GatherDimensionNumbers(offset_dims=(1, 2, 3), collapsed_slice_dims=(1, 3), start_index_map=(1, 3))
        out = lax.gather(
            lax.reshape(out, folded), np.repeat(np.arange(KVH, dtype=np.int32)[:, None], 2, axis=1), diagonal,
            slice_sizes=(NQ, 1, G, 1, HD), mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
        )  # [KVH, NQ, G, HD]
        out = lax.reshape(lax.transpose(out, (1, 0, 2, 3)), (NQ, H, HD))
        # No step visited a dead row's output block: what it holds is not zeros.
        live = lax.gt(lax.index_in_dim(meta, 4, keepdims=False), np.zeros((NQ,), np.int32))
        return lax.select(lax.broadcast_in_dim(live, out.shape, (0,)), out, lax.full_like(out, 0))
    out = out.reshape(NT, groups, tile, fold, G, fold, HD)
    out = out[:, :, :, jnp.arange(fold), :, jnp.arange(fold), :]  # [fold, NT, groups, tile, G, HD]
    return out.transpose(1, 3, 2, 0, 4, 5).reshape(NT * tile, H, HD)[:NQ]
