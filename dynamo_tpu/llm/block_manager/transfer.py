"""Device↔host block transfer: the ``block_copy.cu`` equivalent.

Ref: lib/llm/src/kernels/block_copy.cu (758 LoC of vectorized strided copy
kernels) + block/transfer/cuda.rs. On TPU the same job is a jitted XLA
gather/scatter (XLA emits the optimal DMA) + ``jax.device_get/put`` across
PCIe. Jitted once per cache shape; block id is a traced scalar so every block
reuses the same executable.

A block in transit keeps its heads apart — ``[L, BS, KVH, HD]`` (stacks:
``[L, n, BS, KVH, HD]``) — whatever the pool's layout: the pool merges them
into lanes (``KvCacheArrays``), and every function here reshapes the block
it moves, at the boundary, never the pool.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.kv_cache import (
    KvCacheArrays,
    QuantKv,
    dequantize_kv,
    merge_heads,
    quantize_kv_rows,
    split_heads,
)


def _has_v(cache: KvCacheArrays) -> bool:
    # MLA caches carry everything in the latent ``k`` array; ``v`` is a
    # [L,1,1,1] placeholder that must not be block-indexed.
    return cache.v.shape[1:] == cache.k.shape[1:]


# int8 caches cross the transfer boundary as real-valued blocks: gather
# dequantizes, scatter requantizes. Payload format (host numpy / device
# stacks) is therefore identical for quantized and plain caches — KVBM
# tiers and disagg pulls interoperate across workers with different
# kv_cache_dtype settings. Requantizing a dequantized row recomputes the
# same scale to float rounding, so round-trips are stable to within one
# int8 code step.


@partial(jax.jit, static_argnames=("kv_heads", "dtype"))
def _gather(pool, ids: jax.Array, *, kv_heads: int, dtype=None) -> jax.Array:
    """Pool [L, N, BS, KVH*HD] × one id or ``[n]`` ids → the block
    ``[L, BS, KVH, HD]`` or the stack ``[L, n, BS, KVH, HD]``; a quantized
    pool dequantizes to ``dtype``."""
    if isinstance(pool, QuantKv):
        return dequantize_kv(QuantKv(pool.q[:, ids], pool.scale[:, ids]), dtype)
    return split_heads(pool[:, ids], kv_heads)


@partial(jax.jit, donate_argnums=(0,))
def _scatter(pool, ids: jax.Array, blocks: jax.Array):
    """The inverse of ``_gather``: blocks with their heads apart go back
    into the pool's merged lanes (requantized for a quantized pool)."""
    if isinstance(pool, QuantKv):
        qk = quantize_kv_rows(blocks)
        return QuantKv(pool.q.at[:, ids].set(qk.q), pool.scale.at[:, ids].set(qk.scale))
    return pool.at[:, ids].set(merge_heads(blocks))


def gather_blocks(cache: KvCacheArrays, block_id: int) -> Tuple[np.ndarray, np.ndarray]:
    """Device block → host numpy (device_get performs the DMA)."""
    k_dev, v_dev = gather_blocks_async(cache, block_id)
    if v_dev is None:
        return np.asarray(jax.device_get(k_dev)), np.zeros((0,), dtype=cache.k.dtype)
    return np.asarray(jax.device_get(k_dev)), np.asarray(jax.device_get(v_dev))


def gather_blocks_async(cache: KvCacheArrays, block_id: int):
    """Device-side snapshot of one block — NO host sync. The gather
    dispatch is queued before any later write to the block (single device
    stream), so the returned device arrays are a consistent copy even
    though the caller reuses the block immediately; the host transfer
    happens when the offload queue drains (KvbmManager.flush_pending)."""
    bid = jnp.int32(block_id)
    k = _gather(cache.k, bid, kv_heads=cache.kv_heads, dtype=jnp.float32)
    v = _gather(cache.v, bid, kv_heads=cache.kv_heads, dtype=jnp.float32) if _has_v(cache) else None
    return k, v


def scatter_blocks(cache: KvCacheArrays, block_id: int, k: np.ndarray, v: np.ndarray) -> None:
    """Host numpy → device block (in-place on the cache handle)."""
    dtype = jnp.float32 if isinstance(cache.k, QuantKv) else None  # a quantized pool requantizes real values
    bid = jnp.int32(block_id)
    cache.k = _scatter(cache.k, bid, jnp.asarray(k, dtype=dtype))
    if _has_v(cache):
        cache.v = _scatter(cache.v, bid, jnp.asarray(v, dtype=dtype))


# ---------------------------------------------------------------------------
# Device-native block movement (the NIXL data-plane role): blocks never
# leave the accelerator. A stack is [L, n, BS, KVH, HD]: the same gather and
# scatter as one block, over ``[n]`` ids (one fused DMA each).
# ---------------------------------------------------------------------------


def gather_blocks_device(cache: KvCacheArrays, block_ids) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Stack blocks into fresh device arrays (no host round-trip). The copy
    is independent of the cache, so the source blocks may be released
    immediately while the stack awaits a remote pull."""
    bids = jnp.asarray(list(block_ids), dtype=jnp.int32)
    k = _gather(cache.k, bids, kv_heads=cache.kv_heads, dtype=jnp.bfloat16)
    v = _gather(cache.v, bids, kv_heads=cache.kv_heads, dtype=jnp.bfloat16) if _has_v(cache) else None
    return k, v


def scatter_blocks_device(cache: KvCacheArrays, block_ids, k_stack: jax.Array, v_stack) -> None:
    """Write stacked device blocks into the cache (in-place on the handle)."""
    bids = jnp.asarray(list(block_ids), dtype=jnp.int32)
    cache.k = _scatter(cache.k, bids, k_stack)
    if v_stack is not None and _has_v(cache):
        cache.v = _scatter(cache.v, bids, v_stack)


@jax.jit
def _copy_between(src_k, src_v, dst_k, dst_v, src_ids, dst_ids):
    return dst_k.at[:, dst_ids].set(src_k[:, src_ids]), dst_v.at[:, dst_ids].set(src_v[:, src_ids])


@jax.jit
def _copy_one(src_k, dst_k, src_ids, dst_ids):
    return dst_k.at[:, dst_ids].set(src_k[:, src_ids])


def copy_blocks_between(src: KvCacheArrays, src_ids, dst: KvCacheArrays, dst_ids) -> None:
    """Same-process cache→cache block copy, entirely on device — the
    fast path when prefill and decode engines share a host process
    (ref: NIXL NVLink same-node transfers, dynamo_flow.md S8-S10)."""
    s = jnp.asarray(list(src_ids), dtype=jnp.int32)
    d = jnp.asarray(list(dst_ids), dtype=jnp.int32)
    src_q = isinstance(src.k, QuantKv)
    dst_q = isinstance(dst.k, QuantKv)
    if src_q and dst_q:
        # Quantized→quantized: move codes + scales directly, no requant.
        dst.k = QuantKv(dst.k.q.at[:, d].set(src.k.q[:, s]), dst.k.scale.at[:, d].set(src.k.scale[:, s]))
        dst.v = QuantKv(dst.v.q.at[:, d].set(src.v.q[:, s]), dst.v.scale.at[:, d].set(src.v.scale[:, s]))
        return
    if src_q or dst_q:
        k_stack, v_stack = gather_blocks_device(src, list(src_ids))
        scatter_blocks_device(dst, list(dst_ids), k_stack, v_stack)
        return
    if _has_v(src):
        dst.k, dst.v = _copy_between(src.k, src.v, dst.k, dst.v, s, d)
    else:
        dst.k = _copy_one(src.k, dst.k, s, d)
