"""Pallas TPU paged flash-decode attention.

The XLA decode path gathers the cached prefix through the block table —
``kc[block_tables]`` — which materializes the gathered copy in HBM: every
byte of prefix KV moves three times (read at gather, write of the copy,
read by the attention dot). ``decode_multi`` hoists that gather to once
per window, but the packed buffer still costs a full extra read+write per
window and pins multi-GB buffers at wide batch. This kernel is the role
FlashAttention/paged-attention plays inside the reference's GPU engines
(SURVEY.md §1 L5; /root/reference/lib/llm/src/block_manager/ is the
block-table owner there): attention reads each prefix page from HBM into
VMEM exactly once, and nothing is ever written back.

Design notes (v5e; from the decode ablations of the rounds before PR 1,
not measured on today's code):
- **Pages ARE the pipeline blocks.** The grid is ``(B, W)`` — one program
  per (sequence, table slot) — and the page fetch is a plain BlockSpec
  whose index_map reads the block id from the scalar-prefetched table.
  Pallas's grid pipeline double-buffers the fetches; there are no manual
  DMAs. This only pays at large pages: at ``block_size=16`` the per-page
  issue/latency cost exceeds the 19 ns the 16 KB transfer needs, which is
  exactly why the r4 hand-rolled kernel lost 3× to the XLA gather and was
  deleted. At 256-token pages (256 KB per K page) the fetch is
  bandwidth-bound. Big pages are the TPU-native choice (same conclusion
  as vLLM's TPU backend); the scheduler's block accounting is already
  ``block_size``-agnostic.
- **Ragged for free.** Slots past a sequence's true length point at the
  reserved scratch block 0; consecutive identical block indices skip the
  refetch in the pipeline, so a short sequence in a wide-bucketed table
  costs one wasted page fetch, not W. Compute for dead slots is skipped
  with ``pl.when``.
- **Block-diagonal GQA fold.** Per page the kernel runs TWO dots, not
  2·KVH tiny ones: the caller scatters q into a block-diagonal
  ``Wq[B, KVH*G, KVH*HD]`` (zeros off-block) so
  ``scores = Wq[b] · k_pageᵀ`` yields exact per-head scores (off-block
  lanes hit zeros) in one MXU-shaped ``[KVH*G, 512]×[512, BS]`` matmul.
  The ×KVH FLOP overhead is immaterial — decode attention has ~100×
  MXU headroom; bytes are the budget. The lanes-vs-lanes contraction
  (cache pages are token-major ``[BS, KVH*HD]``) costs an in-kernel
  transpose that would matter in a compute-bound kernel and does not
  here.
- Returns UNnormalized online-softmax partials ``(m, l, acc)`` in the
  ``_attend_piece`` layout so the decode window's in-register piece
  merges outside the kernel via ``_merge_pieces``, identically to the
  XLA path.

On non-TPU backends the kernel runs in interpreter mode so unit tests
exercise the identical code path (``interpret=True``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    tables_ref,  # SMEM [B, W] i32 — block ids (already layer-offset)
    lens_ref,  # SMEM [B] i32 — prefix length per row (0 = inactive)
    wq_ref,  # VMEM [1, KVG, KVHD] — block-diagonal folded queries, this b
    k_ref,  # VMEM [1, BS, KVH*HD] — this (b, w)'s K page (merged-head lanes)
    v_ref,  # VMEM [1, BS, KVH*HD]
    m_ref,  # VMEM [1, KVG, 1] f32 out
    l_ref,  # VMEM [1, KVG, 1] f32 out
    acc_ref,  # VMEM [1, KVG, KVHD] f32 out
    *,
    block_size: int,
    scale: float,
):
    b, w = pl.program_id(0), pl.program_id(1)
    kv_len = lens_ref[b]
    bs = block_size

    @pl.when(w == 0)
    def _init():
        m_ref[0] = jnp.full(m_ref.shape[1:], NEG_INF, jnp.float32)
        l_ref[0] = jnp.zeros(l_ref.shape[1:], jnp.float32)
        acc_ref[0] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

    # Tokens this page holds: [w*bs, w*bs + bs) — compute only if any are
    # inside the row's true prefix.
    @pl.when(w * bs < kv_len)
    def _compute():
        wq = wq_ref[0]  # [KVG, KVHD]
        rows, merged = wq.shape
        k = k_ref[0]  # [BS, KVH*HD] — merged lanes, as the pool stores them
        v = v_ref[0]
        s = (
            lax.dot_general(
                wq, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
        )  # [KVG, BS]
        kpos = w * bs + lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[0]  # [KVG, 1]
        l_prev = l_ref[0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [KVG, KVHD]
        m_ref[0] = m_new
        l_ref[0] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[0] = acc_ref[0] * alpha + pv


@functools.partial(
    jax.jit, static_argnames=("num_kv_heads", "block_size", "interpret")
)
def paged_decode_partials(
    q: jax.Array,  # [B, H, HD] post-rope current-token queries
    k_pages: jax.Array,  # [NP, BS, KVH*HD] layer-flat page pool
    v_pages: jax.Array,
    tables: jax.Array,  # [B, W] i32 — page ids, layer-offset, padded slots → 0
    lengths: jax.Array,  # [B] i32 — true prefix length (0 = inactive row)
    *,
    num_kv_heads: int,
    block_size: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefix-piece decode attention over the paged cache.

    Returns ``(m, l, acc)`` — UNnormalized online-softmax partials shaped
    ``[B, KVH, G]`` / ``[B, KVH, G]`` / ``[B, KVH, G, HD]`` f32, matching
    ``llama._attend_piece`` so the caller merges with the window piece via
    ``llama._merge_pieces``. Rows with ``lengths == 0`` come back as the
    empty piece (m = -inf, l = 0) and drop out of the merge.
    """
    B, H, HD = q.shape
    KVH = num_kv_heads
    G = H // KVH
    KVG, KVHD = KVH * G, KVH * HD
    W = tables.shape[1]

    # Block-diagonal fold: Wq[b, (kvh, g), (kvh', hd)] = q · 1[kvh == kvh'].
    q_r = q.reshape(B, KVH, G, HD)
    eye = jnp.eye(KVH, dtype=q.dtype)[:, None, :, None]  # [KVH, 1, KVH, 1]
    wq = (q_r[:, :, :, None, :] * eye[None]).reshape(B, KVG, KVHD)

    # The pool is stored with (KVH, HD) merged into lanes — the page the
    # BlockSpec reads (the layout contract: ``KvCacheArrays``) — so the
    # pages go to the kernel untouched.
    BS = k_pages.shape[1]
    assert k_pages.shape[2] == KVHD, (k_pages.shape, KVH, HD)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=[
            pl.BlockSpec((1, KVG, KVHD), lambda b, w, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, BS, KVHD), lambda b, w, t, ln: (t[b, w], 0, 0)),
            pl.BlockSpec((1, BS, KVHD), lambda b, w, t, ln: (t[b, w], 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, KVG, 1), lambda b, w, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, KVG, 1), lambda b, w, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, KVG, KVHD), lambda b, w, t, ln: (b, 0, 0)),
        ),
    )
    m, l, acc = pl.pallas_call(
        functools.partial(
            _paged_kernel, block_size=block_size, scale=HD**-0.5
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, KVG, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KVG, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KVG, KVHD), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), wq, k_pages, v_pages)

    m = m.reshape(B, KVH, G)
    l = l.reshape(B, KVH, G)
    # acc rows live in their head's diagonal block: [B, (kvh, g), (kvh, hd)].
    acc = acc.reshape(B, KVH, G, KVH, HD)
    acc = acc[:, jnp.arange(KVH), :, jnp.arange(KVH), :]  # [KVH, B, G, HD]
    acc = acc.transpose(1, 0, 2, 3)  # [B, KVH, G, HD]
    return m, l, acc
