"""Ragged prefill-chunk attention over a GATHERED prefix: the chunk-over-
[prefix ; chunk] piece of phase-separated prefill and of MIXED
prefill+decode steps where no Pallas kernel serves the pool
(``llama.resolve_attention_impl`` says ``"gather"``: ``auto`` off the TPU, a
mesh whose KV heads do not divide, an int8 pool under ``paged``). Wherever
one does (``"megakernel"``, ``"paged"``) a chunk walks the megakernel's tiles
instead (``llama.chunk_walks_tiles``, PERF.md section 6 PR 52) and nothing
here runs.

A ragged batch row is a ``(start, len)`` run of tokens over the paged KV
cache: ``start`` (= ``cache_len``) tokens are already materialized behind a
block table, ``len`` (= ``valid_len``) fresh tokens attend causally within
the chunk and fully over the cached prefix. Decode entries are just
length-1 rows of the same shape — the mixed step (models/llama.py
``mixed_step``) carries them through the in-register two-piece path while
this module handles the chunk rows.

Two backends, numerically interchangeable:
- **XLA** (default off-TPU): one masked softmax over the concatenated
  ``[prefix ; chunk]`` keys — the width-bucketed gather bounds the prefix
  extent, the mask covers fresh and continuation chunks alike.
- **Pallas flash** (``ModelConfig.prefill_impl`` ``"flash"``, or ``auto`` on
  a TPU, with ``attention_impl="gather"``: chip_smoke.py's parity phase and
  an operator's explicit choice reach it): the chunk's causal
  self-attention runs in the flash kernel (prefill.py — scores never leave
  VMEM) and the cached-prefix piece is an online-softmax partial of float32
  scores in HBM, merged outside the kernel; fresh chunks
  (``has_prefix=False``) statically skip the prefix piece altogether.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def ragged_chunk_attention(
    q: jax.Array,  # [T, H, HD] post-rope chunk queries
    k_new: jax.Array,  # [T, KVH, HD] post-rope chunk keys
    v_new: jax.Array,  # [T, KVH, HD]
    k_ctx: Optional[jax.Array],  # [ctx, KVH, HD] gathered cached prefix (None iff flash+fresh)
    v_ctx: Optional[jax.Array],
    valid_len: jax.Array,  # scalar i32 — the row's ``len``
    cache_len: jax.Array,  # scalar i32 — the row's ``start``
    *,
    num_kv_heads: int,
    use_flash: bool = False,
    has_prefix: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Attention for one ragged chunk row over ``[cached prefix ; chunk]``.

    Returns ``[T, H, HD]``. The caller gathers ``k_ctx``/``v_ctx`` through
    its width-bucketed block table (the gather stays O(true prefix), not
    O(max_seq_len)); on the flash path with ``has_prefix=False`` the prefix
    arguments may be ``None`` and no gather is needed at all.
    """
    T, H, HD = q.shape
    kvh = num_kv_heads
    G = H // kvh
    scale = HD**-0.5

    if use_flash:
        from dynamo_tpu.engine.attention.prefill import (
            flash_chunk_attention,
            merge_attention_pieces,
        )

        from dynamo_tpu.engine.sharding import HEADS, over_tp

        # Under a tp mesh the kernel runs per shard over its local heads.
        flash = over_tp(
            flash_chunk_attention, kvh, (HEADS, HEADS, HEADS, P()), (HEADS, HEADS, HEADS),
            interpret=interpret,
        )
        out2, m2, l2 = flash(q, k_new, v_new, valid_len)
        if not has_prefix:
            return out2
        # Cached-prefix partial (online-softmax state), merged with the
        # kernel's chunk piece outside the kernel.
        ctx = k_ctx.shape[0]
        key_pos = jnp.arange(ctx, dtype=jnp.int32)
        qg = q.reshape(T, kvh, G, HD)
        s = jnp.einsum("tkgd,skd->ktgs", qg, k_ctx).astype(jnp.float32) * scale
        s = jnp.where((key_pos < cache_len)[None, None, None, :], s, -1e30)
        m1 = jnp.max(s, axis=-1)  # [KVH, T, G]
        p = jnp.exp(s - m1[..., None])
        l1 = jnp.sum(p, axis=-1)
        acc1 = jnp.einsum("ktgs,skd->ktgd", p.astype(v_ctx.dtype), v_ctx).astype(jnp.float32)
        return merge_attention_pieces(out2, m2, l2, m1, l1, acc1)

    # XLA path: full masked softmax over [prefix ; chunk]. ``has_prefix``
    # is a no-op here — the prefix mask (key_pos < cache_len) covers fresh
    # chunks (cache_len == 0 masks everything), so one executable serves
    # both and the callers keep it traced.
    ctx = k_ctx.shape[0]
    key_pos = jnp.arange(ctx, dtype=jnp.int32)
    chunk_q = jnp.arange(T, dtype=jnp.int32)
    valid_q = chunk_q < valid_len
    prefix_mask = jnp.broadcast_to(key_pos[None, :] < cache_len, (T, ctx))  # [T, ctx]
    chunk_mask = (chunk_q[None, :] <= chunk_q[:, None]) & valid_q[None, :]  # [T, T]
    mask = jnp.concatenate([prefix_mask, chunk_mask], axis=1)  # [T, ctx+T]

    qg = q.reshape(T, kvh, G, HD)
    k_all = jnp.concatenate([k_ctx, k_new], axis=0)
    v_all = jnp.concatenate([v_ctx, v_new], axis=0)
    scores = jnp.einsum("tkgd,skd->ktgs", qg, k_all).astype(jnp.float32) * scale
    scores = jnp.where(mask[None, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("ktgs,skd->tkgd", probs, v_all)
    return out.reshape(T, H, HD)
