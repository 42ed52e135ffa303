"""Pallas TPU fused prefill (flash) attention.

The XLA prefill path materializes f32 scores ``[KVH, T, G, ctx+T]`` per
layer plus a gathered copy of the cached context — at 2K tokens that is
GBs of HBM traffic per layer. This kernel is the role FlashAttention plays inside the
reference's engines (SURVEY.md §1 L5; anchor
/root/reference/docs/benchmarks/pre_deployment_profiling.md:54): blocked
K/V with an online softmax, scores never leave VMEM.

Design notes (v5e, measured with tools in tools/):
- Head-major layout: the caller transposes the chunk K/V to
  ``[KVH, T, HD]`` / K to ``[KVH, HD, T]`` (K pre-transposed so both
  matmuls are MXU-natural — contracting q's lane dim against kᵀ's sublane
  dim; contracting lanes-vs-lanes forces an in-kernel transpose that
  halves throughput, measured).
- Grouped queries ride as rows: q is ``[KVH, T*G, HD]`` and a (kvh, qb)
  program computes ``[BQ*G, BK]`` score tiles — GQA never materializes
  repeated KV heads.
- Causal + validity masking happens on the f32 tile in VMEM; the k-block
  loop stops at the causal frontier of the q block, so the triangle's
  upper half is never computed.
- The kernel also returns the online-softmax state ``(m, l)`` per row so
  a cached-prefix piece (paged KV, gathered by XLA bounded to the true
  prefix width) merges outside the kernel. Fresh prefills (cache_len==0,
  the serving-hot path) statically skip that piece altogether.

Measured (llama-3.2-1b shapes, KVH=8 G=4 HD=64, T=2048, v5e): 40.8
TFLOP/s causal — ~21× the two-piece XLA path at equal shapes.
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


def _chunk_kernel(
    len_ref,  # SMEM [1] i32 — valid_len (keys/queries beyond are padding)
    q_ref,  # VMEM [1, BQ*G, HD]
    kt_ref,  # VMEM [1, HD, T] — whole chunk K, pre-transposed
    v_ref,  # VMEM [1, T, HD]
    o_ref,  # VMEM [1, BQ*G, HD]
    m_ref,  # VMEM [1, BQ*G, 1] f32 — row max (online-softmax state)
    l_ref,  # VMEM [1, BQ*G, 1] f32 — row sum
    *,
    block_q: int,
    block_k: int,
    chunk_len: int,
    groups: int,
    scale: float,
):
    qb = pl.program_id(1)
    valid_len = len_ref[0]
    q = q_ref[0]  # [BQG, HD]
    rows = q.shape[0]
    hd = q.shape[1]
    m = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((rows, 1), jnp.float32)
    acc = jnp.zeros((rows, hd), jnp.float32)
    # Query position of each row: rows are (t, g) pairs, g minor.
    tq = qb * block_q + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // groups

    # Only k blocks at or below the causal frontier of this q block.
    nk = (qb * block_q + block_q + block_k - 1) // block_k

    def body(j, carry):
        m, l, acc = carry
        if block_k == chunk_len:
            # Single k block (small buckets): no dynamic slice — lane-dim
            # offsets must be provably 128-aligned, which j*block_k is not
            # for block_k < 128 (Mosaic rejects the load).
            kt = kt_ref[0]  # [HD, T]
            v = v_ref[0]  # [T, HD]
        else:
            start = pl.multiple_of(j * block_k, block_k)
            kt = kt_ref[0, :, pl.ds(start, block_k)]  # [HD, BK]
            v = v_ref[0, pl.ds(start, block_k), :]  # [BK, HD]
        s = (
            lax.dot_general(q, kt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            * scale
        )  # [BQG, BK]
        kpos = j * block_k + lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        s = jnp.where((kpos <= tq) & (kpos < valid_len), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(0, nk, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    m_ref[0] = m
    l_ref[0] = l


def _pick_blocks(T: int, groups: int) -> Tuple[int, int]:
    """Block sizes: BQ*G ≈ 1024 rows (sweep-optimal on v5e), BK = 512.
    T is a power-of-two bucket, so divisibility holds by construction.
    BK must be ≥128 (lane-aligned dynamic slices) — below that the kernel
    takes the whole chunk as one k block."""
    target = max(1024 // max(groups, 1), 128)
    bq = 1 << (target.bit_length() - 1)  # pow2 ≤ target
    bq = max(1, min(bq, T))
    while T % bq:
        bq //= 2
    bk = min(512, T)
    while T % bk:
        bk //= 2
    if bk < 128:
        bk = T  # single block — no in-kernel dynamic slicing
    return bq, bk


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "interpret"))
def flash_chunk_attention(
    q: jax.Array,  # [T, H, HD] post-rope
    k_new: jax.Array,  # [T, KVH, HD] post-rope
    v_new: jax.Array,  # [T, KVH, HD]
    valid_len: jax.Array,  # scalar i32
    *,
    num_kv_heads: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Causal chunk self-attention with online softmax.

    Returns ``(out [T, H, HD], m [T, KVH, G], l [T, KVH, G])`` — the
    normalized output plus softmax state for merging a cached-prefix
    piece via :func:`merge_attention_pieces`.
    """
    T, H, HD = q.shape
    KVH = num_kv_heads
    G = H // KVH
    BQ, BK = _pick_blocks(T, G)
    BQG = BQ * G
    nq = T // BQ

    # Head-major fold: rows of head kvh are its (t, g) query pairs.
    q_r = q.reshape(T, KVH, G, HD).transpose(1, 0, 2, 3).reshape(KVH, T * G, HD)
    kt = k_new.transpose(1, 2, 0)  # [KVH, HD, T]
    v_r = v_new.transpose(1, 0, 2)  # [KVH, T, HD]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(KVH, nq),
        in_specs=[
            pl.BlockSpec((1, BQG, HD), lambda h, i, *_: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, HD, T), lambda h, i, *_: (h, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T, HD), lambda h, i, *_: (h, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, BQG, HD), lambda h, i, *_: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, BQG, 1), lambda h, i, *_: (h, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, BQG, 1), lambda h, i, *_: (h, i, 0), memory_space=pltpu.VMEM),
        ),
    )
    out, m, l = pl.pallas_call(
        functools.partial(
            _chunk_kernel, block_q=BQ, block_k=BK, chunk_len=T, groups=G, scale=HD**-0.5
        ),
        out_shape=(
            jax.ShapeDtypeStruct((KVH, T * G, HD), q.dtype),
            jax.ShapeDtypeStruct((KVH, T * G, 1), jnp.float32),
            jax.ShapeDtypeStruct((KVH, T * G, 1), jnp.float32),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
    )(jnp.asarray([valid_len], dtype=jnp.int32), q_r, kt, v_r)

    out = out.reshape(KVH, T, G, HD).transpose(1, 0, 2, 3).reshape(T, H, HD)
    m = m.reshape(KVH, T, G).transpose(1, 0, 2)  # [T, KVH, G]
    l = l.reshape(KVH, T, G).transpose(1, 0, 2)
    return out, m, l


def merge_attention_pieces(
    out2: jax.Array,  # [T, H, HD] — normalized kernel output
    m2: jax.Array,  # [T, KVH, G]
    l2: jax.Array,
    m1: jax.Array,  # [KVH, T, G] — XLA prefix piece (llama.prefill `piece` layout)
    l1: jax.Array,
    acc1: jax.Array,  # [KVH, T, G, HD] f32 — UNnormalized prefix accumulator
) -> jax.Array:
    """Close the online softmax across [cached prefix ; chunk] pieces."""
    T, H, HD = out2.shape
    KVH = m1.shape[0]
    G = H // KVH
    m2t = m2.transpose(1, 0, 2)  # [KVH, T, G]
    l2t = l2.transpose(1, 0, 2)
    acc2 = out2.reshape(T, KVH, G, HD).transpose(1, 0, 2, 3).astype(jnp.float32) * l2t[..., None]
    m_t = jnp.maximum(m1, m2t)
    a1 = jnp.exp(m1 - m_t)
    a2 = jnp.exp(m2t - m_t)
    l_t = l1 * a1 + l2t * a2
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    out = acc / jnp.maximum(l_t, 1e-30)[..., None]  # [KVH, T, G, HD]
    return out.transpose(1, 0, 2, 3).reshape(T, H, HD).astype(out2.dtype)
