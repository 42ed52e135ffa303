#!/usr/bin/env python3
"""Micro-benchmark of one layer's attention. ``--study chunk`` (PERF.md §6, PR
31): a mixed step's, a chunk of 256 queries over a paged prefix and its own
keys, beside 32 decode rows of 12 pages, at two pool widths — ``KVH*HD`` 1,024
lanes (Mistral-7B: 32/8 heads of 128) and 4,096 (EvaByte: 32/32 heads of 128).
The chunk's table is 16 slots wide or each of ``--widths`` (EvaByte's prompt
tables are 16 and 20: ``--lanes 4096 --widths 16 20 --prefix 256 1152 2432``,
PERF.md §6, PR 52). ``--study rows`` (PERF.md §6, PR 38): the decode rows'
launch alone, by how many of a bucket's rows are live and how much of their
table they fill.

Forms, each a ``lax.scan`` over L layers of a layer-flat pool, timed whole and
divided by L (the queries of layer l+1 follow from layer l's output, so nothing hoists):

- ``walk``:        one launch of ``ragged_paged_attention`` over all 288
                   queries, a grid row a query (the program before PR 31).
- ``tile<T>``:     the chunk as tiles of T queries, its dots per lane group
                   (``megakernel.lane_fold``), and the decode rows a query a
                   grid row: two launches (what ``llama.mixed_step`` runs).
- ``tile<T>fold``: the same with every KV head folded block-diagonally into
                   one group, as a length-1 row's are.
- ``chunk<T>`` / ``rows``: the two launches of ``tile<T>`` apart.
- ``paged``:       the chunk where no kernel serves the pool and
                   ``prefill_impl`` is ``flash`` (``attention_impl="paged"``'s
                   own chunk path until PR 52: the prefix fetched through
                   ``llama._gather_kv``, flash kernel for the chunk, XLA score
                   product for the prefix); no decode rows.
- ``pagedrows``:   the decode rows on ``attention_impl="paged"``'s path
                   (``paged_decode_partials`` merged with the current token's
                   piece).

Which forms a cell's mixed step is since PR 52 (``llama.chunk_walks_tiles``: a
chunk walks tiles wherever a kernel serves the pool): ``mistral-7b-w8.chat``
and ``mixtral-8x7b-d3.chat-sat`` ``chunk256`` + ``rows`` at 1,024 lanes;
``evabyte-d16.doc-bytes`` ``chunk256`` + ``pagedrows`` at 4,096 lanes
(``paged`` + ``pagedrows`` before it); ``granite-4.0-h-small-d10-e36.chat-many``
and ``zaya1-8b-d20.reason`` a tile walk and ``rows`` through ``hybrid.py``'s
mixers at their own lanes (1,024 and 256); ``dots3-note-prev-d5-e32.long-notes``
none of them (its latent attention is XLA's, ``models/latent.py``).

The rows study takes shapes ``live:pages:bucket:width`` (live rows, full pages
each, the batch bucket, the table's width) and windows (the fresh keys a row
brings: the carry rows of a ``decode_multi`` window, 0 for a single step), at
the first of ``--lanes``, and reads

- ``rows``:        the launch as the step programs make it: padded rows dead,
                   the grid the list of live groups of pages
                   (``megakernel.build_work``), ``megakernel.pages_per_step``
                   pages a step or each of ``--pages-per-step`` (PERF.md §6,
                   PR 47; ``--heads 8 --lanes 256`` is ZAYA1's latent).
- ``rows_walk``:   the same kernel handed every group of every bucket row, the
                   padded rows live with no prefix: at a page a step the
                   ``bucket x (width + 1)`` steps the static grid took before PR 38.
- ``rows_parent``: with ``--parent DIR`` (a checkout of another commit), that
                   commit's kernel on ``rows_walk``'s inputs at a page a step.

    chiprun -- python tools/attn_chunk_bench.py
    chiprun -- python tools/attn_chunk_bench.py --study rows [--parent .parent]
    JAX_PLATFORMS=cpu python tools/attn_chunk_bench.py --tiny [--study rows]   # control flow only

Prints one JSON line per reading (µs a layer) and writes them to
``chiprun_out/attn_chunk_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.attention import megakernel as mk
from dynamo_tpu.engine.attention.decode import paged_decode_partials
from dynamo_tpu.engine.attention.ragged import ragged_chunk_attention
from dynamo_tpu.engine.models import llama


def build(form, tile, *, S, B, W, N, L, KVH, BS, prefix, d_prefix, interpret):
    """``fn(q [S+B, H, HD], k, v [S+B, KVH, HD], k_pool, v_pool [L*N, BS, KVH*HD])``
    -> the last layer's attention output, by ``form``."""
    i32 = jnp.int32
    s_iq, d_iq = jnp.arange(S, dtype=i32), jnp.arange(B, dtype=i32)
    p_table = jnp.arange(1, W + 1, dtype=i32)  # the chunk's pages
    d_tables = (W + 1 + (d_iq[:, None] * W + jnp.arange(W, dtype=i32)[None]) % (N - W - 1)).astype(i32)
    zeros = jnp.zeros((S,), i32)
    p_meta = mk.build_meta(zeros, jnp.full((S,), prefix, i32), zeros, s_iq + 1, jnp.ones((S,), i32))
    d_meta = mk.build_meta(d_iq, jnp.full((B,), d_prefix, i32), d_iq, d_iq + 1, jnp.ones((B,), i32))
    kw = dict(num_kv_heads=KVH, block_size=BS, interpret=interpret)

    def chunk(q, k, v, kp, vp, l):
        return mk.ragged_paged_attention(q[:S], k[:S], v[:S], kp, vp, (p_table + l * N)[None], p_meta, tile=tile, **kw)

    def rows(q, k, v, kp, vp, l):
        return mk.ragged_paged_attention(q[S:], k[S:], v[S:], kp, vp, d_tables + l * N, d_meta, **kw)

    def walk(q, k, v, kp, vp, l):
        tables = jnp.zeros((1 + B, W), i32).at[0].set(p_table).at[1:].set(d_tables) + l * N
        meta = mk.build_meta(
            jnp.concatenate([zeros, 1 + d_iq]), jnp.concatenate([p_meta[1], d_meta[1]]),
            jnp.concatenate([zeros, S + d_iq]), jnp.concatenate([s_iq + 1, S + d_iq + 1]), jnp.ones((S + B,), i32),
        )
        return mk.ragged_paged_attention(q, k, v, kp, vp, tables, meta, **kw)

    def paged(q, k, v, kp, vp, l):
        ctx = [llama._gather_kv(p, p_table + l * N, q.dtype).reshape(W * BS, KVH, -1) for p in (kp, vp)]
        return ragged_chunk_attention(
            q[:S], k[:S], v[:S], *ctx, jnp.int32(S), jnp.int32(prefix),
            num_kv_heads=KVH, use_flash=True, has_prefix=prefix > 0, interpret=interpret,
        )

    def pagedrows(q, k, v, kp, vp, l):
        prefix = paged_decode_partials(q[S:], kp, vp, d_tables + l * N, d_meta[1], **kw)
        qg = q[S:].reshape(B, KVH, -1, q.shape[-1])
        own = llama._attend_piece(qg, k[S:, None], v[S:, None], jnp.ones((B, 1), bool), q.shape[-1] ** -0.5)
        return llama._merge_pieces(*prefix, *own).reshape(B, -1, q.shape[-1])

    def both(*a):
        return jnp.concatenate([chunk(*a), rows(*a)])

    # The form's layer, and the queries it writes.
    layer, lo, hi = {"walk": (walk, 0, S + B), "tile": (both, 0, S + B), "chunk": (chunk, 0, S), "rows": (rows, S, S + B),
                     "paged": (paged, 0, S), "pagedrows": (pagedrows, S, S + B)}[form]

    def fn(q, k, v, kp, vp):
        # The queries are carried in float32 and rounded once a layer, so that
        # two forms differ by their attention alone.
        def body(q32, l):
            out = layer(q32.astype(q.dtype), k, v, kp, vp, l)
            return q32.at[lo:hi].add(0.01 * out.astype(jnp.float32)), None

        return lax.scan(body, q.astype(jnp.float32), jnp.arange(L, dtype=i32))[0]

    return fn, lo, hi


def walk_work(bucket, width, pages_per_step=1):
    """``build_work``'s list with every group of slots of every bucket row in
    it and one more a row: at one page a step the steps of the static grid
    ``(bucket, width + 1)``, a slot past a row's prefix one that fetches the
    table's entry and computes nothing."""
    row = jnp.arange(bucket, dtype=jnp.int32)[:, None] << mk._ROW_SHIFT
    groups = -(-width // pages_per_step) + 1
    items = (row | jnp.arange(groups, dtype=jnp.int32)[None] * pages_per_step).reshape(-1)
    return jnp.concatenate([jnp.full((1,), items.shape[0], jnp.int32), items])


def build_rows(form, kernel, *, live, pages, B, W, window, N, L, KVH, BS, interpret, P=1):
    """``fn(q [B, H, HD], k, v [B*(window+1), KVH, HD], k_pool, v_pool)`` -> the
    last layer's output: ``live`` of ``B`` rows hold ``pages`` full pages less
    half of the last in a table ``W`` wide, each with its ``window + 1`` fresh
    keys as a window's last step has them."""
    i32 = jnp.int32
    iq = jnp.arange(B, dtype=i32)
    is_live = iq < live
    tables = jnp.where(is_live[:, None] & (jnp.arange(W)[None] < pages), 1 + (iq[:, None] * pages + jnp.arange(W, dtype=i32)[None]) % (N - 1), 0)
    first = iq * (window + 1)
    prefix = jnp.where(is_live, pages * BS - BS // 2, 0)
    if form == "rows":
        meta = mk.build_meta(iq, prefix, first, first + window + 1, is_live)
        work = (mk.build_work(prefix, is_live, W, BS, P),)
    else:
        meta = mk.build_meta(iq, prefix, first, first + window + 1, jnp.ones((B,), i32))
        # The parent takes the list of its own kernel: a page an item.
        work = (walk_work(B, W, P if form == "rows_walk" else 1),)

    def fn(q, k, v, kp, vp):
        def body(q32, l):
            out = kernel(q32.astype(q.dtype), k, v, kp, vp, tables + l * N, meta, *work,
                         num_kv_heads=KVH, block_size=BS, interpret=interpret)
            return q32 + 0.01 * out.astype(jnp.float32), None

        return lax.scan(body, q.astype(jnp.float32), jnp.arange(L, dtype=i32))[0]

    return fn


def rows_study(a, say, *, lanes, N, L, H, HD, BS, dtype, on_tpu):
    kernels = {"rows": mk.ragged_paged_attention, "rows_walk": mk.ragged_paged_attention}
    if a.parent:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "parent_megakernel", os.path.join(a.parent, "dynamo_tpu/engine/attention/megakernel.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        kernels["rows_parent"] = parent.ragged_paged_attention
    KVH = lanes // HD
    keys = jax.random.split(jax.random.PRNGKey(lanes), 5)
    kp = jax.random.normal(keys[3], (L * N, BS, lanes), dtype)
    vp = jax.random.normal(keys[4], (L * N, BS, lanes), dtype)
    page_bytes = BS * lanes * jnp.dtype(dtype).itemsize
    step_bytes, step_pages = mk.ROWS_STEP_BYTES, mk.ROWS_STEP_PAGES
    for shape in a.rows:
        live, pages, B, W = (int(x) for x in shape.split(":"))
        for window in a.windows:
            q = jax.random.normal(keys[0], (B, H, HD), dtype)
            k = jax.random.normal(keys[1], (B * (window + 1), KVH, HD), dtype)
            v = jax.random.normal(keys[2], (B * (window + 1), KVH, HD), dtype)
            ref = None
            # Pages a step: the program's own, or each of --pages-per-step (the
            # constants pages_per_step reads, moved for the reading).
            wants = a.pages_per_step or [None]
            for want in wants:
                if want:
                    mk.ROWS_STEP_BYTES, mk.ROWS_STEP_PAGES = want * page_bytes, max(step_pages, want)
                    mk.ragged_paged_attention.clear_cache()
                P = mk.pages_per_step(BS, lanes, jnp.dtype(dtype).itemsize, W)
                for form, kernel in kernels.items():
                    if form == "rows_parent" and want != wants[0]:
                        continue  # the parent's kernel reads no constant of this one: once a shape
                    at = dict(lanes=lanes, heads=H, shape=shape, window=window, form=form, pages_per_step=1 if form == "rows_parent" else P)
                    try:
                        fn = build_rows(form, kernel, live=live, pages=pages, B=B, W=W, window=window, N=N, L=L, KVH=KVH,
                                        BS=BS, P=P, interpret=not on_tpu)
                        us, out = measure(fn, (q, k, v, kp, vp), a.iters)
                    except Exception as e:  # a step the compiler refuses is a reading too
                        say(**at, error=f"{type(e).__name__}: {str(e)[:300]}")
                        continue
                    out = jnp.asarray(out, jnp.float32)[:live]
                    ref = out if ref is None else ref
                    say(**at, us_per_layer=us / L, kv_mb=live * pages * page_bytes * 2 / 1e6,
                        max_rel_diff_vs_rows=float(jnp.max(jnp.abs(ref - out)) / (jnp.max(jnp.abs(ref)) + 1e-9)))
    mk.ROWS_STEP_BYTES, mk.ROWS_STEP_PAGES = step_bytes, step_pages


def measure(fn, args, iters):
    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--lanes", type=int, nargs="*", default=[1024, 4096])
    ap.add_argument("--prefix", type=int, nargs="*", default=[0, 512, 1408])
    ap.add_argument("--widths", type=int, nargs="*", default=[],
                    help="the chunk study at each of these widths of the chunk's table (slots), not at 16 alone")
    ap.add_argument("--tiles", type=int, nargs="*", default=[32, 64, 128, 256])
    ap.add_argument("--fold-tiles", type=int, nargs="*", default=[16, 32])
    ap.add_argument("--study", choices=["chunk", "rows"], default="chunk")
    ap.add_argument("--rows", nargs="*", default=["5:4:32:8", "5:4:32:12", "32:12:32:16", "4:4:4:4"],
                    help="live:pages:bucket:width")
    ap.add_argument("--windows", type=int, nargs="*", default=[0, 8])
    ap.add_argument("--parent", help="a checkout of another commit, whose kernel is read beside this one's")
    ap.add_argument("--heads", type=int, default=32, help="query heads (the rows study; ZAYA1's latent has 8 over 2)")
    ap.add_argument("--pages-per-step", type=int, nargs="*", default=[],
                    help="the rows study at each of these pages a grid step, not at megakernel.pages_per_step's own")
    a = ap.parse_args()
    on_tpu = jax.devices()[0].platform == "tpu"
    if a.tiny:
        S, B, W, N, L, H, HD, BS, dtype = 32, 4, 4, 16, 2, 4, 16, 16, jnp.float32
        a.lanes, a.prefix, a.tiles, a.fold_tiles, a.iters = [32, 64], [0, 24], [16, 32], [16], 1
        a.rows, a.windows, a.pages_per_step = ["2:2:4:4", "4:3:4:4"], [0, 2], [1, 2]
    else:
        S, B, W, N, L, H, HD, BS, dtype = 256, 32, 16, 64, a.layers, 32, 128, 128, jnp.bfloat16
        H = a.heads if a.study == "rows" else H
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/attn_chunk_bench.jsonl", "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    widths = a.widths or [W]
    say(device=str(jax.devices()[0].device_kind), S=S, B=B, W=widths if a.study == "chunk" else W, L=L, H=H, HD=HD, BS=BS,
        dtype=str(jnp.dtype(dtype)))
    if a.study == "rows":
        return rows_study(a, say, lanes=a.lanes[0], N=N, L=L, H=H, HD=HD, BS=BS, dtype=dtype, on_tpu=on_tpu)
    lane_fold = mk.lane_fold
    for lanes in a.lanes:
        KVH = lanes // HD
        keys = jax.random.split(jax.random.PRNGKey(lanes), 5)
        q = jax.random.normal(keys[0], (S + B, H, HD), dtype)
        k = jax.random.normal(keys[1], (S + B, KVH, HD), dtype)
        v = jax.random.normal(keys[2], (S + B, KVH, HD), dtype)
        kp = jax.random.normal(keys[3], (L * N, BS, lanes), dtype)
        vp = jax.random.normal(keys[4], (L * N, BS, lanes), dtype)
        for W, prefix in ((w, p) for w in widths for p in a.prefix if p <= w * BS):
            d_prefix = min(12, W) * BS - BS // 2
            forms = [("walk", 1, False), ("rows", 1, False), ("paged", 1, False), ("pagedrows", 1, False)]
            forms += [(f, t, False) for t in a.tiles for f in ("tile", "chunk")]
            forms += [("tile", t, True) for t in a.fold_tiles]
            ref = None
            for form, tile, fold in forms:
                name = form + (str(tile) if tile > 1 else "") + ("fold" if fold else "")
                mk.lane_fold = (lambda kvh, hd: kvh) if fold else lane_fold
                mk.ragged_paged_attention.clear_cache()  # its traces are keyed by the tile, not by the fold
                try:
                    fn, lo, hi = build(form, tile, S=S, B=B, W=W, N=N, L=L, KVH=KVH, BS=BS, prefix=prefix,
                               d_prefix=d_prefix, interpret=not on_tpu)
                    us, out = measure(fn, (q, k, v, kp, vp), a.iters)
                except Exception as e:  # a tile the compiler refuses is a reading too
                    say(lanes=lanes, width=W, prefix=prefix, form=name, error=f"{type(e).__name__}: {str(e)[:300]}")
                    continue
                finally:
                    mk.lane_fold = lane_fold
                out = jnp.asarray(out, jnp.float32)
                if form == "walk":
                    ref = out
                err = float(jnp.max(jnp.abs(ref[lo:hi] - out[lo:hi])) / (jnp.max(jnp.abs(ref[lo:hi])) + 1e-9))
                say(lanes=lanes, width=W, prefix=prefix, form=name, us_per_layer=us / L, max_rel_diff_vs_walk=err)


if __name__ == "__main__":
    main()
