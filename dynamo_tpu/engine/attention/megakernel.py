"""Ragged paged-attention megakernel: ONE Pallas launch for the whole
mixed prefill+decode step's attention.

Why this exists: the r4 per-piece Pallas paged kernel issued 2+ launches
per layer (chunk flash kernel + decode prefix kernel), and the XLA gather
fallback moves triple traffic (gather read + packed-copy write + attend
re-read). The per-launch dispatch cost that motivated amortizing launches
is not measured on a directly attached chip (ROADMAP S2; blueprint:
"Ragged Paged Attention", arxiv 2604.15464).

``ragged_paged_attention``: one launch per layer serves EVERY row of a
mixed step. A row is a ``(start, len)`` run of queries over ``[paged
prefix ; fresh keys]``: prefill chunks are wide rows, decode entries are
length-1 rows, and both share one grid — ``(query, page)`` — with

- *scalar-prefetched block tables* (the page fetch is a plain BlockSpec
  whose index_map reads the table; Pallas double-buffers the HBM→VMEM
  streams, nothing is ever written back — vs the gather's 3× traffic),
- the *block-diagonal GQA fold* proven in ``attention/decode.py`` (one
  MXU-shaped dot per page instead of G tiny ones; decode attention has
  ~100× MXU headroom, bytes are the budget),
- ``pl.when`` skipping for dead slots: padded queries and
  table slots past a row's true length cost no page fetch and no
  compute, so ragged batches cost bytes, not bucket width,
- an int8-KV dequant-in-VMEM path (per-(token, head) scales streamed
  alongside the int8 codes and expanded over lanes in-kernel), so
  capacity-mode deployments keep the fused path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


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


def _online_update(m_ref, l_ref, acc_ref, s, v):
    """Fold one score tile + value tile into the online-softmax scratch."""
    m_prev = m_ref[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    pv = lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[:] = m_new
    l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[:] = acc_ref[:] * alpha + pv


def _mega_kernel(
    tables_ref,  # SMEM [R, W] i32 — per-row page ids (layer-offset, dead → 0)
    meta_ref,  # SMEM [5, NQ] i32 — build_meta layout
    wq_ref,  # VMEM [1, KVG, KVHD] — this query's block-diagonal fold
    ke_ref,  # VMEM [CK, KVHD] — ALL fresh keys (lane-merged), loaded once
    ve_ref,  # VMEM [CK, KVHD]
    k_ref,  # VMEM [1, BS, KVHD] — this (query, slot)'s K page
    v_ref,
    *rest,  # (ks_ref, vs_ref)? o_ref, m_ref, l_ref, acc_ref
    block_size: int,
    num_slots: int,
    scale: float,
    quant: bool,
):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    nq, w = pl.program_id(0), pl.program_id(1)
    prefix_len = meta_ref[1, nq]
    e_start = meta_ref[2, nq]
    e_end = meta_ref[3, nq]
    live = meta_ref[4, nq] > 0
    bs = block_size
    wq = wq_ref[0]  # [KVG, KVHD]
    rows = wq.shape[0]

    @pl.when(w == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:] = jnp.zeros(acc_ref.shape, jnp.float32)

    # Paged-prefix piece: slot w holds tokens [w*bs, w*bs+bs) of this
    # query's row. Dead queries and slots past the true prefix are skipped
    # entirely — no page fetch is wasted on bucket width (consecutive
    # identical table entries reuse the pipelined fetch, so a short row in
    # a wide bucket costs one scratch-page fetch, not W).
    @pl.when(live & (w < num_slots) & (w * bs < prefix_len))
    def _page():
        if quant:
            # int8 dequant in VMEM: per-(token, head) scales expand over
            # the HD lanes (lane j of the merged (kvh, hd) axis carries
            # head j // HD). The codes stream at 1 byte/value — the whole
            # point of int8 KV is capacity, and the fused path keeps it.
            hd = k_ref.shape[2] // ks_ref.shape[2]
            k = k_ref[0].astype(wq.dtype) * jnp.repeat(
                ks_ref[0], hd, axis=-1
            ).astype(wq.dtype)
            v = v_ref[0].astype(wq.dtype) * jnp.repeat(
                vs_ref[0], hd, axis=-1
            ).astype(wq.dtype)
        else:
            k = k_ref[0]  # [BS, KVHD]
            v = v_ref[0]
        s = (
            lax.dot_general(
                wq, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [KVG, BS]
        kpos = w * bs + lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        s = jnp.where(kpos < prefix_len, s, NEG_INF)
        _online_update(m_ref, l_ref, acc_ref, s, v)

    # Final slot: the in-flight (not-yet-cached) keys — a chunk query's
    # causal window over its own chunk, a decode query's current token, a
    # window query's carry rows — then close the softmax and normalize.
    @pl.when(w == num_slots)
    def _fresh_and_final():
        @pl.when(live & (e_end > e_start))
        def _fresh():
            ke = ke_ref[:]  # [CK, KVHD]
            ve = ve_ref[:]
            s = (
                lax.dot_general(
                    wq, ke, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [KVG, CK]
            cpos = lax.broadcasted_iota(jnp.int32, (rows, ke.shape[0]), 1)
            s = jnp.where((cpos >= e_start) & (cpos < e_end), s, NEG_INF)
            _online_update(m_ref, l_ref, acc_ref, s, ve)

        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("num_kv_heads", "block_size", "interpret")
)
def ragged_paged_attention(
    q: jax.Array,  # [NQ, H, HD] post-rope queries (chunk rows then decode rows)
    k_extra: jax.Array,  # [CK, KVH, HD] in-flight keys (chunk K, window rows, current tokens)
    v_extra: jax.Array,
    k_pages,  # [NP, BS, KVH*HD] layer-flat page pool, or QuantKv (scales [NP, BS, KVH])
    v_pages,
    tables: jax.Array,  # [R, W] i32 — per-sequence-row page ids (layer-offset)
    meta: jax.Array,  # [5, NQ] i32 — build_meta
    *,
    num_kv_heads: int,
    block_size: int,
    interpret: bool = False,
) -> jax.Array:
    """Attention for a whole ragged batch over [paged prefix ; fresh keys]
    in ONE kernel launch. Returns normalized ``[NQ, H, HD]`` — the prefix
    pages and the fresh piece merge inside the kernel's online softmax, so
    no external ``_merge_pieces`` is needed and no gathered prefix copy is
    ever materialized in HBM.

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

    # Block-diagonal GQA fold (attention/decode.py): off-block lanes hit
    # zeros, so one [KVG, KVHD]×[KVHD, BS] dot yields exact per-head
    # scores. The ×KVH query-byte inflation is immaterial next to the KV
    # bytes the kernel exists to save.
    q_r = q.reshape(NQ, KVH, G, HD)
    eye = jnp.eye(KVH, dtype=q.dtype)[:, None, :, None]
    wq = (q_r[:, :, :, None, :] * eye[None]).reshape(NQ, KVG, KVHD)

    ke = k_extra.reshape(CK, KVHD)
    ve = v_extra.reshape(CK, KVHD)

    BS = k_pages.shape[1]
    assert k_pages.shape[2] == KVHD, (k_pages.shape, KVH, HD)

    def page_idx(nq, w, t, mt):
        return (t[mt[0, nq], jnp.minimum(w, W - 1)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, KVG, KVHD), lambda nq, w, t, mt: (nq, 0, 0)),
        pl.BlockSpec((CK, KVHD), lambda nq, w, t, mt: (0, 0)),
        pl.BlockSpec((CK, KVHD), lambda nq, w, t, mt: (0, 0)),
        pl.BlockSpec((1, BS, KVHD), page_idx),
        pl.BlockSpec((1, BS, KVHD), page_idx),
    ]
    if quant:
        in_specs += [
            pl.BlockSpec((1, BS, KVH), page_idx),
            pl.BlockSpec((1, BS, KVH), page_idx),
        ]
        args = [wq, ke, ve, k_pages.q, v_pages.q, k_pages.scale, v_pages.scale]
    else:
        args = [wq, ke, ve, k_pages, v_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NQ, W + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVG, KVHD), lambda nq, w, t, mt: (nq, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVG, 1), jnp.float32),
            pltpu.VMEM((KVG, 1), jnp.float32),
            pltpu.VMEM((KVG, KVHD), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _mega_kernel,
            block_size=block_size,
            num_slots=W,
            scale=HD**-0.5,
            quant=quant,
        ),
        out_shape=jax.ShapeDtypeStruct((NQ, KVG, KVHD), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(tables.astype(jnp.int32), meta.astype(jnp.int32), *args)

    # Each query's output lives in its head's diagonal block of the fold.
    out = out.reshape(NQ, KVH, G, KVH, HD)
    out = out[:, jnp.arange(KVH), :, jnp.arange(KVH), :]  # [KVH, NQ, G, HD]
    return out.transpose(1, 0, 2, 3).reshape(NQ, H, HD)
