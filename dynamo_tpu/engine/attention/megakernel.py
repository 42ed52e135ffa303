"""Ragged paged-attention megakernel: ONE Pallas launch for the whole
mixed prefill+decode step's attention, plus a fused multi-step decode
window (one launch spanning N steps × L layers).

Why this exists: the r4 per-piece Pallas paged kernel issued 2+ launches
per layer (chunk flash kernel + decode prefix kernel), and the XLA gather
fallback moves triple traffic (gather read + packed-copy write + attend
re-read). The per-launch dispatch cost that motivated amortizing launches
is not measured on a directly attached chip (ROADMAP S2; blueprint:
"Ragged Paged Attention", arxiv 2604.15464):

**Tier 1 — ``ragged_paged_attention``** (this module's workhorse): one
launch per layer serves EVERY row of a mixed step. A row is a
``(start, len)`` run of queries over ``[paged prefix ; fresh keys]``:
prefill chunks are wide rows, decode entries are length-1 rows, and both
share one grid — ``(query, page)`` — with

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

**Tier 2 — ``fused_decode_window``**: one ``pallas_call`` whose grid
spans ``(num_steps, num_layers)`` runs an ENTIRE greedy decode window —
embedding, per-layer matmuls + rope + paged attention + SwiGLU, lm_head,
argmax, and the KV writes — with the sampled token fed back through VMEM
scratch between grid steps (TPU grids execute sequentially, so the
carry is exact). Exactly ONE kernel launch per N-step window; the
``decode_multi`` dispatch-overhead term disappears entirely and the
prefix pages are the only KV bytes read. Gated to VMEM-resident scale
(``fused_window_fits``): weights + cache must fit on-chip, which covers
draft/small models today; larger models use Tier 1 per step. Compiled-
TPU status: does NOT compile — verified in interpreter mode only
(tier-1 CI); the v5e compiler refuses ``decode_multi_fused`` at the
``tiny`` preset (``'tpu.iota' op result #0 must be vector of integer or
index values, but got 'vector<1x8xf32>'``, from ``_rope``'s
``lax.iota(jnp.float32, …)``; more may sit behind it). The gate is a
byte budget, not a device check: ``out=tiny`` or ``--draft-model tiny``
on a chip selects this tier compiled and fails there; at 1B it is off
(2.5 GB ≫ 12 MiB). ROADMAP D3 owns the decision.

``trace_launch_count()`` counts ``pallas_call`` invocations at TRACE
time: a fused window executable must contain exactly ONE launch site
(asserted in CI via the flight recorder's ``fused_window_pallas_launches``
gauge) so dispatch-amortization regressions — someone un-fusing the loop
back into per-step or per-piece kernels — fail loudly.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Trace-time pallas_call counter (see module docstring). Incremented once
# per launch SITE traced, so `delta == 1` across tracing a whole fused
# window proves the executable contains a single fused launch.
_TRACE_LAUNCHES = 0


def _count_launch() -> None:
    global _TRACE_LAUNCHES
    _TRACE_LAUNCHES += 1


def trace_launch_count() -> int:
    """Total pallas_call sites traced by this module since import."""
    return _TRACE_LAUNCHES


# ---------------------------------------------------------------------------
# Tier 1: ragged paged-attention megakernel (one launch per layer)
# ---------------------------------------------------------------------------


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
    _count_launch()
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


# ---------------------------------------------------------------------------
# Tier 2: fused multi-step decode window (one launch per window)
# ---------------------------------------------------------------------------


def fused_window_fits(
    param_bytes: int, cache_bytes: int, budget_bytes: Optional[int] = None
) -> bool:
    """VMEM-residency gate for the fused window: the kernel keeps weights,
    embedding/head, and the paged cache on-chip, so it only serves models
    whose working set fits (draft/small models; the tier-1 test scale).
    Larger deployments fall back to the per-step ragged megakernel, which
    streams pages per launch. Override via
    ``DYNAMO_TPU_FUSED_WINDOW_MAX_BYTES``."""
    import os

    if budget_bytes is None:
        budget_bytes = int(
            os.environ.get("DYNAMO_TPU_FUSED_WINDOW_MAX_BYTES", 12 << 20)
        )
    return param_bytes + cache_bytes <= budget_bytes


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """apply_rope's exact math (split halves, not interleaved) in-kernel.
    ``lax.iota`` instead of ``jnp.arange``: arange materializes a constant
    the kernel would capture (Pallas rejects captured consts)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (lax.iota(jnp.float32, hd // 2) * 2.0 / hd))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., hd/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    n = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (n * w.astype(jnp.float32)).astype(x.dtype)


def _fused_window_kernel(
    # scalar prefetch
    tables_ref,  # SMEM [B, W] i32 — block ids (NOT layer-offset)
    pos0_ref,  # SMEM [B] i32 — write slot of the first window token
    act_ref,  # SMEM [B] i32
    tok0_ref,  # SMEM [B] i32 — step-0 input tokens
    rows0_ref,  # SMEM [B] i32 — guided mask-pool row at window start (0 = allow-all)
    # tensor inputs (whole arrays resident; the VMEM gate guards size):
    # 12 weights, then (sampled? temps/tks/tps/uniforms), then
    # (guided? mask_pool/next_pool), then k_in/v_in — parsed from *rest so
    # the cache operands stay LAST and the in/out alias indices stay a
    # fixed formula of n_tensor_in.
    embed_ref,  # [V, D]
    head_ref,  # [D, V]
    fnorm_ref,  # [D]
    anorm_ref,  # [L, D]
    mnorm_ref,  # [L, D]
    wq_ref,  # [L, D, HQ]
    wk_ref,  # [L, D, HKV]
    wv_ref,  # [L, D, HKV]
    wo_ref,  # [L, HQ, D]
    wg_ref,  # [L, D, F]
    wu_ref,  # [L, D, F]
    wd_ref,  # [L, F, D]
    *rest,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    block_size: int,
    rms_eps: float,
    theta: float,
    sampled: bool,
    guided: bool,
):
    r = 0
    if sampled:
        temps_ref, tks_ref, tps_ref, unif_ref = rest[r : r + 4]
        r += 4
    if guided:
        mask_ref, next_ref = rest[r : r + 2]  # [P, ceil(V/32)] u32, [P, V] i32
        r += 2
    (
        k_in_ref,  # [L, N, BS, KVH*HD] (aliased to k_out off-interpret)
        v_in_ref,
        # outputs
        tok_out_ref,  # [NSTEPS, B] i32
        k_out_ref,  # [L, N, BS, KVH*HD]
        v_out_ref,
        # scratch
        h_ref,  # VMEM [B, D] wdtype — the inter-layer residual carry
        tok_ref,  # SMEM [B] i32 — on-device token feedback between steps
        row_ref,  # SMEM [B] i32 — guided FSM row carry (unused unless guided)
    ) = rest[r : r + 8]

    i, l = pl.program_id(0), pl.program_id(1)
    L = pl.num_programs(1)
    B = h_ref.shape[0]
    W = tables_ref.shape[1]
    H, KVH, HD, bs = num_heads, num_kv_heads, head_dim, block_size
    G = H // KVH
    scale = HD**-0.5

    # One defensive full-cache copy at window start: correct whether or not
    # the runtime honored the input/output alias (interpret mode does not).
    @pl.when((i == 0) & (l == 0))
    def _seed_cache():
        k_out_ref[:] = k_in_ref[:]
        v_out_ref[:] = v_in_ref[:]
        for b in range(B):
            row_ref[b] = rows0_ref[b]

    # Step entry: embed this step's input tokens — step 0 from the host,
    # later steps from the PREVIOUS grid step's argmax (VMEM/SMEM carry:
    # the on-device token feedback that makes one launch span the window).
    @pl.when(l == 0)
    def _embed():
        for b in range(B):
            tok = jnp.where(i == 0, tok0_ref[b], tok_ref[b])
            h_ref[b, :] = embed_ref[tok, :].astype(h_ref.dtype)

    h = h_ref[:]  # [B, D]
    x = _rms(h, anorm_ref[l], rms_eps)
    q = jnp.dot(x, wq_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
    k = jnp.dot(x, wk_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.dot(x, wv_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
    q = q.reshape(B, H, HD)
    k = k.reshape(B, KVH, HD)
    v = v.reshape(B, KVH, HD)
    positions = jnp.stack([pos0_ref[b] for b in range(B)]) + i  # [B]
    q = _rope(q, positions, theta)
    k = _rope(k, positions, theta)

    # Write-before-attend: this step's K/V rows land in the cache first,
    # then attention masks kpos <= pos — identical math to the in-register
    # current-token piece, and it makes the cache the single source of
    # truth for the window carry (parity with decode_multi's final fused
    # scatter is asserted down to cache contents).
    for b in range(B):
        pos_b = positions[b]
        live = act_ref[b] > 0
        slot = jnp.where(live, pos_b, 0)
        blk = jnp.where(live, tables_ref[b, slot // bs], 0)
        off = slot % bs
        k_out_ref[l, blk, off] = k[b].reshape(KVH * HD).astype(k_out_ref.dtype)
        v_out_ref[l, blk, off] = v[b].reshape(KVH * HD).astype(v_out_ref.dtype)

    attn_rows = []
    for b in range(B):
        pages_k = [k_out_ref[l, tables_ref[b, w]] for w in range(W)]
        pages_v = [v_out_ref[l, tables_ref[b, w]] for w in range(W)]
        kb = jnp.concatenate(pages_k, axis=0).astype(x.dtype).reshape(W * bs, KVH, HD)
        vb = jnp.concatenate(pages_v, axis=0).astype(x.dtype).reshape(W * bs, KVH, HD)
        qg = q[b].reshape(KVH, G, HD)
        s = jnp.einsum("kgd,skd->kgs", qg, kb).astype(jnp.float32) * scale
        kpos = lax.iota(jnp.int32, W * bs)
        s = jnp.where(kpos[None, None, :] <= positions[b], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        attn_rows.append(jnp.einsum("kgs,skd->kgd", p, vb).reshape(H * HD))
    attn = jnp.stack(attn_rows)  # [B, HQ]

    h = h + jnp.dot(attn, wo_ref[l], preferred_element_type=jnp.float32).astype(h.dtype)
    x = _rms(h, mnorm_ref[l], rms_eps)
    g = jnp.dot(x, wg_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
    u = jnp.dot(x, wu_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
    mlp = jnp.dot(
        jax.nn.silu(g) * u, wd_ref[l], preferred_element_type=jnp.float32
    ).astype(h.dtype)
    h = h + mlp
    h_ref[:] = h

    # Last layer: head + in-kernel epilogue — guided rows mask against
    # their FSM row's packed allow bitmask (apply_token_masks math),
    # sampled rows draw via the shared reference filter + inverse-CDF on
    # this step's host-precomputed uniform, greedy rows argmax — then the
    # token feeds back for step i+1 and guided rows advance their FSM row
    # through the device-resident next-state pool.
    @pl.when(l == L - 1)
    def _sample():
        from dynamo_tpu.engine.sampling import sample_from_uniforms

        hf = _rms(h_ref[:], fnorm_ref[:], rms_eps)
        logits = jnp.dot(
            hf, head_ref[:], preferred_element_type=jnp.float32
        )  # [B, V] f32
        V = logits.shape[-1]
        if guided:
            rows = jnp.stack([mask_ref[row_ref[b]] for b in range(B)])  # [B, W32]
            vidx = lax.iota(jnp.int32, V)
            words = rows[:, vidx >> 5]  # [B, V] uint32
            bit = jnp.right_shift(words, (vidx & 31).astype(jnp.uint32)) & jnp.uint32(1)
            logits = jnp.where(bit.astype(bool), logits, -jnp.inf)
        if sampled:
            nxt = sample_from_uniforms(
                logits, temps_ref[:], tks_ref[:], tps_ref[:], unif_ref[i, :]
            )
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok_out_ref[i, :] = nxt
        for b in range(B):
            tok_ref[b] = nxt[b]
            if guided:
                row_ref[b] = next_ref[row_ref[b], nxt[b]]


@functools.partial(
    jax.jit,
    static_argnames=("num_steps", "num_heads", "num_kv_heads", "head_dim",
                     "block_size", "rms_eps", "theta", "interpret",
                     "sampled", "guided"),
)
def fused_decode_window(
    embed: jax.Array,  # [V, D]
    head: jax.Array,  # [D, V] (caller resolves tied embeddings)
    final_norm: jax.Array,  # [D]
    attn_norm: jax.Array,  # [L, D]
    mlp_norm: jax.Array,
    wq: jax.Array,  # [L, D, HQ]
    wk: jax.Array,
    wv: jax.Array,
    wo: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    tokens: jax.Array,  # [B] i32
    positions: jax.Array,  # [B] i32
    tables: jax.Array,  # [B, W] i32
    active: jax.Array,  # [B] bool
    temps: Optional[jax.Array] = None,  # [B] f32 (sampled=True)
    top_ks: Optional[jax.Array] = None,  # [B] i32
    top_ps: Optional[jax.Array] = None,  # [B] f32
    uniforms: Optional[jax.Array] = None,  # [num_steps, B] f32 (make_window_uniforms)
    guided_rows: Optional[jax.Array] = None,  # [B] i32 mask-pool rows (guided=True)
    mask_pool: Optional[jax.Array] = None,  # [P, ceil(V/32)] uint32
    next_pool: Optional[jax.Array] = None,  # [P, V] i32 FSM next-row pool
    *,
    num_steps: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    block_size: int,
    rms_eps: float,
    theta: float,
    interpret: bool = False,
    sampled: bool = False,
    guided: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """N decode steps in ONE kernel launch (grid = steps × layers).

    Returns ``(tokens_out [num_steps, B] i32, k_cache, v_cache)`` with the
    window's KV rows written in place — token-for-token AND cache-content
    parity with greedy ``decode_multi`` (tested). The host syncs once per
    window and the device dispatches once per window.

    ``sampled=True`` adds the in-kernel top-k/top-p epilogue: per-row
    packed params plus a host-precomputed ``[num_steps, B]`` uniforms
    operand (sampling.make_window_uniforms — one upload per window, no
    per-step host sync or PRNG threading in-kernel). ``guided=True`` adds
    grammar masking: each row's FSM mask rides the device-resident packed
    allow-bitmask pool, and the FSM advances ON-CHIP between steps through
    the next-state row pool, so guided rows no longer flush the window.
    """
    L = k_cache.shape[0]
    B = tokens.shape[0]
    V, D = embed.shape

    vspec = pl.BlockSpec(memory_space=pltpu.VMEM)
    extra = []
    if sampled:
        extra += [
            temps.astype(jnp.float32), top_ks.astype(jnp.int32),
            top_ps.astype(jnp.float32), uniforms.astype(jnp.float32),
        ]
    if guided:
        extra += [mask_pool, next_pool.astype(jnp.int32)]
    n_tensor_in = 12 + len(extra) + 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(num_steps, L),
        in_specs=[vspec] * n_tensor_in,
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((B, D), embed.dtype),
            pltpu.SMEM((B,), jnp.int32),
            pltpu.SMEM((B,), jnp.int32),
        ],
    )
    kwargs = {}
    if not interpret:
        # Donate the cache buffers into their outputs: zero-copy in-place
        # window writes on device (the kernel still seeds via an explicit
        # copy, harmless on aliased buffers). Interpret mode does not
        # support aliasing; the seed copy keeps it correct there.
        kwargs["input_output_aliases"] = {n_tensor_in - 2 + 5: 1, n_tensor_in - 1 + 5: 2}
    rows0 = guided_rows if guided_rows is not None else jnp.zeros((B,), jnp.int32)
    _count_launch()
    toks, k_new, v_new = pl.pallas_call(
        functools.partial(
            _fused_window_kernel,
            num_heads=num_heads,
            num_kv_heads=num_kv_heads,
            head_dim=head_dim,
            block_size=block_size,
            rms_eps=rms_eps,
            theta=theta,
            sampled=sampled,
            guided=guided,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((num_steps, B), jnp.int32),
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        **kwargs,
    )(
        tables.astype(jnp.int32),
        positions.astype(jnp.int32),
        active.astype(jnp.int32),
        tokens.astype(jnp.int32),
        rows0.astype(jnp.int32),
        embed, head, final_norm, attn_norm, mlp_norm,
        wq, wk, wv, wo, w_gate, w_up, w_down,
        *extra,
        k_cache, v_cache,
    )
    return toks, k_new, v_new


# ---------------------------------------------------------------------------
# Tier 2b: fused speculative window (draft + target verify in ONE launch)
# ---------------------------------------------------------------------------


def _one_token_forward(
    toks,  # [B] i32 — one input token per row
    positions,  # [B] i32 — write slot / attention frontier per row
    act_ref,  # SMEM [B] i32
    tables_ref,  # SMEM [B, W] i32
    k_ref,  # [L, N, BS, KVH*HD] output-aliased cache ref
    v_ref,
    w,  # 12-tuple of weight refs (embed..w_down, fused-window layout)
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    block_size: int,
    rms_eps: float,
    theta: float,
):
    """One token per row through ALL layers of one VMEM-resident model:
    write-before-attend KV at ``positions``, then the same paged-page
    attention math as ``_fused_window_kernel`` (python layer loop instead
    of a grid axis). Returns logits [B, V] f32. Dead rows sink their KV
    write to scratch block 0 and their logits are ignored."""
    (embed_ref, head_ref, fnorm_ref, anorm_ref, mnorm_ref,
     wq_ref, wk_ref, wv_ref, wo_ref, wg_ref, wu_ref, wd_ref) = w
    B = toks.shape[0]
    L = anorm_ref.shape[0]
    W = tables_ref.shape[1]
    H, KVH, HD, bs = num_heads, num_kv_heads, head_dim, block_size
    G = H // KVH
    scale = HD**-0.5

    h = jnp.stack([embed_ref[toks[b], :] for b in range(B)])  # [B, D]
    for l in range(L):
        x = _rms(h, anorm_ref[l], rms_eps)
        q = jnp.dot(x, wq_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        k = jnp.dot(x, wk_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        v = jnp.dot(x, wv_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        q = _rope(q.reshape(B, H, HD), positions, theta)
        k = _rope(k.reshape(B, KVH, HD), positions, theta)
        v = v.reshape(B, KVH, HD)
        for b in range(B):
            live = act_ref[b] > 0
            slot = jnp.where(live, jnp.maximum(positions[b], 0), 0)
            blk = jnp.where(live, tables_ref[b, slot // bs], 0)
            off = slot % bs
            k_ref[l, blk, off] = k[b].reshape(KVH * HD).astype(k_ref.dtype)
            v_ref[l, blk, off] = v[b].reshape(KVH * HD).astype(v_ref.dtype)
        attn_rows = []
        for b in range(B):
            kb = jnp.concatenate(
                [k_ref[l, tables_ref[b, wi]] for wi in range(W)], axis=0
            ).astype(x.dtype).reshape(W * bs, KVH, HD)
            vb = jnp.concatenate(
                [v_ref[l, tables_ref[b, wi]] for wi in range(W)], axis=0
            ).astype(x.dtype).reshape(W * bs, KVH, HD)
            qg = q[b].reshape(KVH, G, HD)
            s = jnp.einsum("kgd,skd->kgs", qg, kb).astype(jnp.float32) * scale
            kpos = lax.iota(jnp.int32, W * bs)
            s = jnp.where(kpos[None, None, :] <= positions[b], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            attn_rows.append(jnp.einsum("kgs,skd->kgd", p, vb).reshape(H * HD))
        attn = jnp.stack(attn_rows)  # [B, HQ]
        h = h + jnp.dot(attn, wo_ref[l], preferred_element_type=jnp.float32).astype(h.dtype)
        x = _rms(h, mnorm_ref[l], rms_eps)
        g = jnp.dot(x, wg_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.dot(x, wu_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        h = h + jnp.dot(
            jax.nn.silu(g) * u, wd_ref[l], preferred_element_type=jnp.float32
        ).astype(h.dtype)
    hf = _rms(h, fnorm_ref[:], rms_eps)
    return jnp.dot(hf, head_ref[:], preferred_element_type=jnp.float32)  # [B, V] f32


def _chunk_forward(
    toks,  # [B, S] i32 — S consecutive tokens per row
    pos0,  # [B] i32 — position of column 0
    act_ref,
    tables_ref,
    k_ref,
    v_ref,
    w,
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    block_size: int,
    rms_eps: float,
    theta: float,
):
    """S-token chunk through ALL layers of one resident model (the target
    verify pass): per layer, every chunk row's K/V lands in the cache
    FIRST, then each row attends causally (kpos ≤ pos0+s) — so in-chunk
    attention reads the cache it just wrote, same write-before-attend
    contract as the single-token forward. Returns logits [B, S, V] f32."""
    (embed_ref, head_ref, fnorm_ref, anorm_ref, mnorm_ref,
     wq_ref, wk_ref, wv_ref, wo_ref, wg_ref, wu_ref, wd_ref) = w
    B, S = toks.shape
    L = anorm_ref.shape[0]
    W = tables_ref.shape[1]
    H, KVH, HD, bs = num_heads, num_kv_heads, head_dim, block_size
    G = H // KVH
    scale = HD**-0.5

    h = jnp.stack(
        [jnp.stack([embed_ref[toks[b, s], :] for s in range(S)]) for b in range(B)]
    )  # [B, S, D]
    positions = pos0[:, None] + lax.iota(jnp.int32, S)[None, :]  # [B, S]
    for l in range(L):
        x = _rms(h, anorm_ref[l], rms_eps)
        q = jnp.dot(x, wq_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        k = jnp.dot(x, wk_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        v = jnp.dot(x, wv_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        q = _rope(q.reshape(B, S, H, HD), positions, theta)
        k = _rope(k.reshape(B, S, KVH, HD), positions, theta)
        v = v.reshape(B, S, KVH, HD)
        for b in range(B):
            live = act_ref[b] > 0
            for s in range(S):
                slot = jnp.where(live, jnp.maximum(positions[b, s], 0), 0)
                blk = jnp.where(live, tables_ref[b, slot // bs], 0)
                off = slot % bs
                k_ref[l, blk, off] = k[b, s].reshape(KVH * HD).astype(k_ref.dtype)
                v_ref[l, blk, off] = v[b, s].reshape(KVH * HD).astype(v_ref.dtype)
        attn_rows = []
        for b in range(B):
            kb = jnp.concatenate(
                [k_ref[l, tables_ref[b, wi]] for wi in range(W)], axis=0
            ).astype(x.dtype).reshape(W * bs, KVH, HD)  # [T, KVH, HD]
            vb = jnp.concatenate(
                [v_ref[l, tables_ref[b, wi]] for wi in range(W)], axis=0
            ).astype(x.dtype).reshape(W * bs, KVH, HD)
            qg = q[b].reshape(S, KVH, G, HD)
            s_sc = jnp.einsum("skgd,tkd->skgt", qg, kb).astype(jnp.float32) * scale
            kpos = lax.iota(jnp.int32, W * bs)
            mask = kpos[None, None, None, :] <= positions[b][:, None, None, None]
            s_sc = jnp.where(mask, s_sc, NEG_INF)
            p = jax.nn.softmax(s_sc, axis=-1).astype(x.dtype)
            attn_rows.append(jnp.einsum("skgt,tkd->skgd", p, vb).reshape(S, H * HD))
        attn = jnp.stack(attn_rows)  # [B, S, HQ]
        h = h + jnp.dot(attn, wo_ref[l], preferred_element_type=jnp.float32).astype(h.dtype)
        x = _rms(h, mnorm_ref[l], rms_eps)
        g = jnp.dot(x, wg_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.dot(x, wu_ref[l], preferred_element_type=jnp.float32).astype(x.dtype)
        h = h + jnp.dot(
            jax.nn.silu(g) * u, wd_ref[l], preferred_element_type=jnp.float32
        ).astype(h.dtype)
    hf = _rms(h, fnorm_ref[:], rms_eps)
    return jnp.dot(hf, head_ref[:], preferred_element_type=jnp.float32)  # [B, S, V]


def _fused_spec_kernel(
    # scalar prefetch (6)
    tables_t_ref,  # SMEM [B, W] i32 — target block ids
    tables_d_ref,  # SMEM [B, W] i32 — draft block ids
    pos0_ref,  # SMEM [B] i32 — position of the last confirmed token
    act_ref,  # SMEM [B] i32
    tok0_ref,  # SMEM [B] i32 — last confirmed token
    xprev0_ref,  # SMEM [B] i32 — token at pos0-1 (draft catch-up feed)
    *rest,
    gamma: int,
    t_num_heads: int,
    t_num_kv_heads: int,
    t_head_dim: int,
    d_num_heads: int,
    d_num_kv_heads: int,
    d_head_dim: int,
    block_size: int,
    t_rms_eps: float,
    d_rms_eps: float,
    t_theta: float,
    d_theta: float,
):
    """One speculative ROUND per grid step, entire window in one launch:
    draft catch-up + γ sampled proposals, target γ+1-token verify chunk,
    inline rejection sampling, and the accepted-burst cursor advance — all
    against the two resident caches. Rejected proposals are never
    rewound: the write cursor retreats to pos+k+1, and every stale row
    beyond it is overwritten by the NEXT round's sequential writes before
    anything attends to it (write-before-attend + monotone positions), so
    rejection costs zero cache traffic."""
    from dynamo_tpu.engine.sampling import filtered_probs_rows, pick_from_probs

    G = gamma
    w_t = rest[0:12]
    w_d = rest[12:24]
    temps_ref, tks_ref, tps_ref, unif_ref = rest[24:28]  # unif: [R, B, 2G+1]
    k_t_in, v_t_in, k_d_in, v_d_in = rest[28:32]
    (toks_out_ref, acc_out_ref, k_t_ref, v_t_ref, k_d_ref, v_d_ref,
     pos_ref, tok_ref, xprev_ref) = rest[32:41]

    r = pl.program_id(0)
    B = pos0_ref.shape[0]
    t_dims = dict(
        num_heads=t_num_heads, num_kv_heads=t_num_kv_heads, head_dim=t_head_dim,
        block_size=block_size, rms_eps=t_rms_eps, theta=t_theta,
    )
    d_dims = dict(
        num_heads=d_num_heads, num_kv_heads=d_num_kv_heads, head_dim=d_head_dim,
        block_size=block_size, rms_eps=d_rms_eps, theta=d_theta,
    )

    @pl.when(r == 0)
    def _seed():
        k_t_ref[:] = k_t_in[:]
        v_t_ref[:] = v_t_in[:]
        k_d_ref[:] = k_d_in[:]
        v_d_ref[:] = v_d_in[:]
        for b in range(B):
            pos_ref[b] = pos0_ref[b]
            tok_ref[b] = tok0_ref[b]
            xprev_ref[b] = xprev0_ref[b]

    pos = jnp.stack([pos_ref[b] for b in range(B)])  # [B]
    tok = jnp.stack([tok_ref[b] for b in range(B)])
    xprev = jnp.stack([xprev_ref[b] for b in range(B)])
    temps, tks, tps = temps_ref[:], tks_ref[:], tps_ref[:]

    # 1. Draft catch-up: re-feed the token at pos-1 unconditionally. For
    # rows whose draft cache already covers pos-1 this deterministically
    # recomputes the same row (idempotent); for rows one short (the all-γ-
    # accepted case) it materializes the missing row. Logits discarded.
    _one_token_forward(
        xprev, pos - 1, act_ref, tables_d_ref, k_d_ref, v_d_ref, w_d, **d_dims
    )

    # 2. Draft proposes γ tokens via the shared reference filter +
    # inverse-CDF on host-precomputed uniforms (slots 0..γ-1).
    props = []
    pds = []
    cur, cur_pos = tok, pos
    for g in range(G):
        logits = _one_token_forward(
            cur, cur_pos, act_ref, tables_d_ref, k_d_ref, v_d_ref, w_d, **d_dims
        )
        dist = filtered_probs_rows(logits, temps, tks, tps)
        x = pick_from_probs(dist, unif_ref[r, :, g])
        props.append(x)
        pds.append(dist)
        cur, cur_pos = x, cur_pos + 1

    # 3. Target verifies [tok, x1..xγ] in one in-kernel chunk pass.
    chunk = jnp.stack([tok] + props, axis=1)  # [B, G+1]
    logits_all = _chunk_forward(
        chunk, pos, act_ref, tables_t_ref, k_t_ref, v_t_ref, w_t, **t_dims
    )  # [B, G+1, V]
    pts = [
        filtered_probs_rows(logits_all[:, s, :], temps, tks, tps)
        for s in range(G + 1)
    ]

    # 4. Rejection sampling (spec_decode.spec_verify math, uniforms from
    # slots γ..2γ-1 for accepts and 2γ for the correction/bonus pick).
    # Greedy rows' one-hot dists reduce every formula to exact argmax
    # agreement + argmax bonus.
    prop_mat = jnp.stack(props, axis=1)  # [B, G]
    accept_cols = []
    for g in range(G):
        x = props[g]
        pt_x = jnp.take_along_axis(pts[g], x[:, None], axis=1)[:, 0]
        pd_x = jnp.take_along_axis(pds[g], x[:, None], axis=1)[:, 0]
        ratio = pt_x / jnp.maximum(pd_x, 1e-20)
        accept_cols.append(unif_ref[r, :, G + g] < jnp.minimum(ratio, 1.0))
    rejected = ~jnp.stack(accept_cols, axis=1)  # [B, G]
    first_rej = jnp.where(
        jnp.any(rejected, axis=1), jnp.argmax(rejected, axis=1), G
    ).astype(jnp.int32)
    idxc = jnp.clip(first_rej, 0, G - 1)
    pt_stack = jnp.stack(pts[:G], axis=1)  # [B, G, V]
    pd_stack = jnp.stack(pds, axis=1)
    pt_k = jnp.take_along_axis(pt_stack, idxc[:, None, None], axis=1)[:, 0]
    pd_k = jnp.take_along_axis(pd_stack, idxc[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(pt_k - pd_k, 0.0)
    rs = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(rs > 1e-20, resid / jnp.maximum(rs, 1e-20), pt_k)
    upick = unif_ref[r, :, 2 * G]
    corr = pick_from_probs(resid, upick)
    bonus = pick_from_probs(pts[G], upick)
    y = jnp.where(first_rej == G, bonus, corr).astype(jnp.int32)

    # 5. Emit this round's proposals + correction/bonus and the accept
    # count; the host replays the cursor to trim at k and handle stops.
    toks_out_ref[r, :, :] = jnp.concatenate([prop_mat, y[:, None]], axis=1)
    acc_out_ref[r, :] = first_rej

    # 6. Accepted-burst cursor advance: pos += k+1, the correction/bonus
    # becomes the next round's feed token, and x_k (or tok when k=0)
    # becomes the catch-up token at the new pos-1.
    xk = jnp.where(
        first_rej >= 1,
        jnp.take_along_axis(prop_mat, jnp.clip(first_rej - 1, 0, G - 1)[:, None], axis=1)[:, 0],
        tok,
    ).astype(jnp.int32)
    for b in range(B):
        pos_ref[b] = pos[b] + first_rej[b] + 1
        tok_ref[b] = y[b]
        xprev_ref[b] = xk[b]


@functools.partial(
    jax.jit,
    static_argnames=(
        "rounds", "gamma", "block_size",
        "t_num_heads", "t_num_kv_heads", "t_head_dim", "t_rms_eps", "t_theta",
        "d_num_heads", "d_num_kv_heads", "d_head_dim", "d_rms_eps", "d_theta",
        "interpret",
    ),
)
def fused_spec_window(
    # target weights (fused-window layout)
    t_embed, t_head, t_fnorm, t_anorm, t_mnorm,
    t_wq, t_wk, t_wv, t_wo, t_wg, t_wu, t_wd,
    # draft weights
    d_embed, d_head, d_fnorm, d_anorm, d_mnorm,
    d_wq, d_wk, d_wv, d_wo, d_wg, d_wu, d_wd,
    k_t: jax.Array,  # [Lt, N, BS, KVHt*HDt] target cache
    v_t: jax.Array,
    k_d: jax.Array,  # draft cache
    v_d: jax.Array,
    tokens: jax.Array,  # [B] i32 — last confirmed token per row
    xprev: jax.Array,  # [B] i32 — token at positions-1 (draft catch-up)
    positions: jax.Array,  # [B] i32 — position of the last confirmed token
    tables_t: jax.Array,  # [B, W] i32
    tables_d: jax.Array,  # [B, W] i32
    active: jax.Array,  # [B] bool
    temps: jax.Array,  # [B] f32
    top_ks: jax.Array,  # [B] i32
    top_ps: jax.Array,  # [B] f32
    uniforms: jax.Array,  # [rounds, B, 2*gamma+1] f32
    *,
    rounds: int,
    gamma: int,
    block_size: int,
    t_num_heads: int,
    t_num_kv_heads: int,
    t_head_dim: int,
    t_rms_eps: float,
    t_theta: float,
    d_num_heads: int,
    d_num_kv_heads: int,
    d_head_dim: int,
    d_rms_eps: float,
    d_theta: float,
    interpret: bool = False,
) -> Tuple[jax.Array, ...]:
    """``rounds`` speculative rounds — draft γ-proposal bursts AND the
    target verify chunks — in ONE Pallas launch (grid = rounds; both
    models' weights and both paged caches VMEM-resident; gated by
    ``fused_window_fits`` over the combined working set).

    Returns ``(tokens_out [rounds, B, γ+1] i32, accepted [rounds, B] i32,
    k_t, v_t, k_d, v_d)``: per round, row b proposed ``tokens_out[r, b,
    :γ]``, accepted the first ``accepted[r, b]`` of them, and appended
    ``tokens_out[r, b, γ]`` as correction/bonus. The host syncs once per
    window and replays cursors (stop conditions, draft-lag accounting)
    from the two small int outputs."""
    B = tokens.shape[0]
    n_tensor_in = 32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(rounds,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n_tensor_in,
        out_specs=tuple(pl.BlockSpec(memory_space=pltpu.VMEM) for _ in range(6)),
        scratch_shapes=[
            pltpu.SMEM((B,), jnp.int32),
            pltpu.SMEM((B,), jnp.int32),
            pltpu.SMEM((B,), jnp.int32),
        ],
    )
    kwargs = {}
    if not interpret:
        # Donate both caches into their outputs (same contract as the
        # plain fused window; the seed copy keeps interpret mode correct).
        kwargs["input_output_aliases"] = {
            n_tensor_in - 4 + 6: 2, n_tensor_in - 3 + 6: 3,
            n_tensor_in - 2 + 6: 4, n_tensor_in - 1 + 6: 5,
        }
    _count_launch()
    return pl.pallas_call(
        functools.partial(
            _fused_spec_kernel,
            gamma=gamma,
            t_num_heads=t_num_heads, t_num_kv_heads=t_num_kv_heads,
            t_head_dim=t_head_dim,
            d_num_heads=d_num_heads, d_num_kv_heads=d_num_kv_heads,
            d_head_dim=d_head_dim,
            block_size=block_size,
            t_rms_eps=t_rms_eps, d_rms_eps=d_rms_eps,
            t_theta=t_theta, d_theta=d_theta,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rounds, B, gamma + 1), jnp.int32),
            jax.ShapeDtypeStruct((rounds, B), jnp.int32),
            jax.ShapeDtypeStruct(k_t.shape, k_t.dtype),
            jax.ShapeDtypeStruct(v_t.shape, v_t.dtype),
            jax.ShapeDtypeStruct(k_d.shape, k_d.dtype),
            jax.ShapeDtypeStruct(v_d.shape, v_d.dtype),
        ),
        grid_spec=grid_spec,
        interpret=interpret,
        **kwargs,
    )(
        tables_t.astype(jnp.int32),
        tables_d.astype(jnp.int32),
        positions.astype(jnp.int32),
        active.astype(jnp.int32),
        tokens.astype(jnp.int32),
        xprev.astype(jnp.int32),
        t_embed, t_head, t_fnorm, t_anorm, t_mnorm,
        t_wq, t_wk, t_wv, t_wo, t_wg, t_wu, t_wd,
        d_embed, d_head, d_fnorm, d_anorm, d_mnorm,
        d_wq, d_wk, d_wv, d_wo, d_wg, d_wu, d_wd,
        temps.astype(jnp.float32), top_ks.astype(jnp.int32),
        top_ps.astype(jnp.float32), uniforms.astype(jnp.float32),
        k_t, v_t, k_d, v_d,
    )
