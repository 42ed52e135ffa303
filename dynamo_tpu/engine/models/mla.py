"""Multi-head latent attention (MLA, DeepSeek-V2/V3 family) over a paged
*latent* KV cache.

The reference serves DeepSeek models through engine adapters (SGLang
DP-attention / TRT-LLM wide-EP recipes, SURVEY.md §2e); here MLA is native.
TPU-first design:

- **Latent cache**: each token stores one row ``[kv_lora_rank + rope_dim]``
  (e.g. 512+64) instead of per-head K/V — ~7× less HBM than GQA-8 at
  head_dim 128, which multiplies the decode batch the HBM can hold.
- **Absorbed projections**: queries are pre-multiplied by W_uk
  (``q_eff = q_nope · W_uk``) so attention contracts directly against the
  latent; values decompress *after* the probability-weighted latent sum
  (``out = (p · c_kv) · W_uv``) — both are MXU matmuls, nothing per-key.
- Same paged block-table layout as the llama family (block 0 = scratch
  sink), so the scheduler, prefix cache, KVBM and disaggregation move MLA
  blocks with zero special-casing.

Cache layout: k_cache [L, N, BS, R] with R = kv_lora_rank +
qk_rope_head_dim — one "head" of width R in the pool's merged-lane layout
(``KvCacheArrays``); v_cache is unused (shape [L, 1, 1, 1]).

Which latent path the scheduler serves. This module is the OLDER, whole-stack
family (``ModelConfig.architecture == "mla"``): every layer alike, full-rank
queries, ``prefill`` / ``decode`` / ``decode_multi`` through the XLA gather
and none of the scheduler's fast paths (no mixed steps, no slots); it is held
by ``tests/test_mla.py`` on the CPU and no benchmark cell runs it. The latent
layers the benchmark serves are the layer-group kinds "mla_full" and
"mla_window" of ``ModelConfig.layer_types`` (``models/latent.py``, driven by
``models/hybrid.py``): low-rank queries, a gate a head, a learned indexer or a
sliding window, through ``mixed_step`` and the slot life-cycle. The absorbed
products are written once, there: this module's rows go through
``latent.absorb`` and ``latent.attend``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.kv_cache import layer_flat
from dynamo_tpu.engine.models.latent import absorb, attend
from dynamo_tpu.engine.models.llama import _gather_kv, _scatter_kv, _mlp, _split_expert_stacks, apply_rope, rms_norm

Params = Dict[str, jax.Array]


def latent_width(config: ModelConfig) -> int:
    return config.kv_lora_rank + config.qk_rope_head_dim


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    c = config
    L, H = c.num_layers, c.num_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    keys = jax.random.split(key, 12)

    def dense(k, shape, scale=None):
        scale = scale if scale is not None else shape[-2] ** -0.5 if len(shape) >= 2 else 0.02
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dtype)

    layers: Dict[str, jax.Array] = {
        "attn_norm": jnp.ones((L, c.hidden_size), dtype=dtype),
        "mlp_norm": jnp.ones((L, c.hidden_size), dtype=dtype),
        "kv_norm": jnp.ones((L, c.kv_lora_rank), dtype=dtype),
        "wq": dense(keys[0], (L, c.hidden_size, H * qk)),
        "w_dkv": dense(keys[1], (L, c.hidden_size, c.kv_lora_rank)),
        "w_kr": dense(keys[2], (L, c.hidden_size, c.qk_rope_head_dim)),
        "w_uk": dense(keys[3], (L, H, c.qk_nope_head_dim, c.kv_lora_rank), scale=c.qk_nope_head_dim**-0.5),
        "w_uv": dense(keys[4], (L, H, c.kv_lora_rank, c.v_head_dim), scale=c.kv_lora_rank**-0.5),
        "wo": dense(keys[5], (L, H * c.v_head_dim, c.hidden_size)),
    }
    if c.num_experts == 0:
        layers.update(
            w_gate=dense(keys[6], (L, c.hidden_size, c.intermediate_size)),
            w_up=dense(keys[7], (L, c.hidden_size, c.intermediate_size)),
            w_down=dense(keys[8], (L, c.intermediate_size, c.hidden_size)),
        )
    else:
        E = c.num_experts
        layers.update(
            router=dense(keys[9], (L, c.hidden_size, E)),
            w_gate=dense(keys[6], (L, E, c.hidden_size, c.intermediate_size)),
            w_up=dense(keys[7], (L, E, c.hidden_size, c.intermediate_size)),
            w_down=dense(keys[8], (L, E, c.intermediate_size, c.hidden_size)),
        )
    params: Params = {
        "embed": dense(keys[10], (c.vocab_size, c.hidden_size), scale=0.02),
        "final_norm": jnp.ones((c.hidden_size,), dtype=dtype),
        "layers": layers,
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = dense(keys[11], (c.hidden_size, c.vocab_size), scale=0.02)
    return params


def _project_q(x: jax.Array, lp, c: ModelConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x [T, D] → (q_eff [T, H, r], q_rope [T, H, rope]) with q_eff absorbed
    through W_uk."""
    T = x.shape[0]
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    q = (x @ lp["wq"]).reshape(T, c.num_heads, qk)
    q_nope = q[..., : c.qk_nope_head_dim]
    q_rope = apply_rope(q[..., c.qk_nope_head_dim :], positions, c.rope_theta)
    return absorb(q_nope, lp["w_uk"]), q_rope


def _latent_kv(x: jax.Array, lp, c: ModelConfig, positions: jax.Array) -> jax.Array:
    """x [T, D] → latent rows [T, R] = [norm(c_kv) ‖ rope(k_rope)]."""
    c_kv = rms_norm(x @ lp["w_dkv"], lp["kv_norm"], c.rms_norm_eps)
    k_rope = apply_rope((x @ lp["w_kr"])[:, None, :], positions, c.rope_theta)[:, 0]
    return jnp.concatenate([c_kv, k_rope], axis=-1)


def _attend_latent(
    q_eff: jax.Array,  # [T, H, r]
    q_rope: jax.Array,  # [T, H, rope]
    latent: jax.Array,  # [S, R]
    mask: jax.Array,  # [T, S]
    lp,
    c: ModelConfig,
) -> jax.Array:
    """→ [T, H * v_head_dim]. The absorbed product is ``latent.attend``'s:
    one set of rows for all queries, or (``latent [T, S, R]``) a set a query."""
    scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    attn_lat = attend(jnp.concatenate([q_eff, q_rope], axis=-1), latent, mask, c.kv_lora_rank, scale)  # weighted latent sum
    out = jnp.einsum("thr,hrv->thv", attn_lat, lp["w_uv"])  # decompress once
    return out.reshape(q_eff.shape[0], c.num_heads * c.v_head_dim)


def prefill(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, R]
    v_cache: jax.Array,  # unused
    tokens: jax.Array,  # [T]
    valid_len: jax.Array,
    cache_len: jax.Array,
    block_table: jax.Array,  # [max_blocks]
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    c = config
    bs = c.block_size
    T = tokens.shape[0]
    ctx = block_table.shape[0] * bs

    h = params["embed"].at[tokens].get(mode="clip")
    positions = cache_len + jnp.arange(T, dtype=jnp.int32)
    valid_q = jnp.arange(T, dtype=jnp.int32) < valid_len
    slots = jnp.where(valid_q, positions, 0)
    tgt_blocks = jnp.where(valid_q, block_table[slots // bs], 0)
    tgt_offs = slots % bs

    # Cache read-only in the scan; the chunk's latent rows come out as ys and
    # ONE fused scatter writes all layers afterwards — a scatter inside the
    # carry forces a full cache copy per layer (measured; see
    # llama.decode_layer_scan). The gather reads a layer-flat [L*N] view with
    # layer-offset tables so the scan never slices the cache per layer
    # (the slice materializes a layer-cache copy per iteration).
    key_pos = jnp.arange(ctx, dtype=jnp.int32)
    prefix_mask = jnp.broadcast_to(key_pos[None, :] < cache_len, (T, ctx))
    chunk_q = jnp.arange(T, dtype=jnp.int32)
    chunk_mask = (chunk_q[None, :] <= chunk_q[:, None]) & valid_q[None, :]
    mask = jnp.concatenate([prefix_mask, chunk_mask], axis=1)  # [T, ctx+T]
    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    scanned, experts = _split_expert_stacks(c, params["layers"])

    def layer_fn(h, xs):
        lp, l = xs
        x = rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
        q_eff, q_rope = _project_q(x, lp, c, positions)
        latent_new = _latent_kv(x, lp, c, positions)  # [T, R]
        latent_ctx = _gather_kv(k_flat, block_table + l * N, h.dtype).reshape(ctx, latent_width(c))
        attn = _attend_latent(
            q_eff, q_rope, jnp.concatenate([latent_ctx, latent_new], axis=0), mask, lp, c
        )
        h = h + attn @ lp["wo"]
        x = rms_norm(h, lp["mlp_norm"], c.rms_norm_eps)
        h = h + _mlp(x, lp, c, valid=valid_q, experts=experts, layer=l)
        return h, latent_new

    h, latent_rows = lax.scan(
        layer_fn, h, (scanned, jnp.arange(c.num_layers, dtype=jnp.int32))
    )
    L = c.num_layers
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, T))
    k_new = _scatter_kv(
        k_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], latent_rows[:, :, None, :]
    )
    last = jnp.maximum(valid_len - 1, 0)
    h_last = rms_norm(h[last], params["final_norm"], c.rms_norm_eps)
    head = params.get("lm_head")
    logits = h_last @ (head if head is not None else params["embed"].T)
    return logits.astype(jnp.float32), k_new, v_cache


def decode(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, R]
    v_cache: jax.Array,  # unused
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, W]
    active: jax.Array,  # [B]
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    c = config
    bs = c.block_size
    B = tokens.shape[0]
    ctx = block_tables.shape[1] * bs
    R = latent_width(c)

    h = params["embed"].at[tokens].get(mode="clip")
    slots = jnp.where(active, positions, 0)
    tgt_blocks = jnp.where(active, jnp.take_along_axis(block_tables, (slots // bs)[:, None], axis=1)[:, 0], 0)
    tgt_offs = slots % bs
    # Cached-prefix mask; the current row's latent is attended in-register
    # and written back with one fused scatter after the scan.
    key_pos = jnp.arange(ctx, dtype=jnp.int32)
    mask = key_pos[None, :] < positions[:, None]
    mask_full = jnp.concatenate([mask, jnp.ones((B, 1), dtype=bool)], axis=1)
    # Layer-flat view: no per-layer cache slice in the scan (see prefill).
    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    scanned, experts = _split_expert_stacks(c, params["layers"])

    def layer_fn(h, xs):
        lp, l = xs
        x = rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
        # dim 0 is the batch here; rope broadcasts per-row positions the same
        # way it broadcasts per-token positions in prefill.
        q_eff, q_rope = _project_q(x, lp, c, positions)
        latent_row = _latent_kv(x, lp, c, positions)  # [B, R]
        latent_ctx = _gather_kv(k_flat, block_tables + l * N, h.dtype).reshape(B, ctx, R)
        latent_full = jnp.concatenate([latent_ctx, latent_row[:, None]], axis=1)
        attn = jax.vmap(
            lambda qe, qr, lat, mb: _attend_latent(qe[None], qr[None], lat, mb[None], lp, c)[0]
        )(q_eff, q_rope, latent_full, mask_full)  # [B, H*v]
        h = h + attn @ lp["wo"]
        x2 = rms_norm(h, lp["mlp_norm"], c.rms_norm_eps)
        h = h + _mlp(x2, lp, c, valid=active, experts=experts, layer=l)
        return h, latent_row

    h, latent_rows = lax.scan(
        layer_fn, h, (scanned, jnp.arange(c.num_layers, dtype=jnp.int32))
    )
    L = c.num_layers
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, B))
    k_new = _scatter_kv(
        k_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], latent_rows[:, :, None, :]
    )
    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    head = params.get("lm_head")
    logits = h @ (head if head is not None else params["embed"].T)
    return logits.astype(jnp.float32), k_new, v_cache


def decode_multi(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, R]
    v_cache: jax.Array,  # unused
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, W] — must cover positions+num_steps
    active: jax.Array,  # [B]
    temps: jax.Array,
    top_ks: jax.Array,
    top_ps: jax.Array,
    rng_key: jax.Array,
    num_steps: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-step decode window (see llama.decode_multi): N steps + sampling
    per dispatch. Returns (tokens_out [num_steps, B], k_cache, v_cache).

    Window-local latent rows: the cache is READ-ONLY for the whole window —
    per-step latent rows accumulate in a small carry and ONE fused scatter
    writes them afterwards (a per-step scatter on the carry forces a full
    latent-cache copy per iteration; see llama.decode_multi)."""
    from dynamo_tpu.engine.sampling import sample_batch

    c = config
    bs = c.block_size
    B = tokens.shape[0]
    L = c.num_layers
    R = latent_width(c)
    N = k_cache.shape[1]
    ctx = block_tables.shape[1] * bs
    k_flat = layer_flat(k_cache)
    key_pos = jnp.arange(ctx, dtype=jnp.int32)
    mask0 = key_pos[None, :] < positions[:, None]  # fixed: cache not written in-window
    scanned, experts = _split_expert_stacks(c, params["layers"])

    def body(i, state):
        toks, lat_win, out, key = state
        poss = positions + i
        h = params["embed"].at[toks].get(mode="clip")
        win_mask = jnp.broadcast_to(
            (jnp.arange(num_steps, dtype=jnp.int32) < i)[None, :], (B, num_steps)
        )
        mask_full = jnp.concatenate([mask0, win_mask, jnp.ones((B, 1), dtype=bool)], axis=1)

        def layer_fn(h, xs):
            lp, l, lwl = xs  # lwl: [w, B, R] this layer's window latent rows
            x = rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
            q_eff, q_rope = _project_q(x, lp, c, poss)
            latent_row = _latent_kv(x, lp, c, poss)  # [B, R]
            latent_ctx = _gather_kv(k_flat, block_tables + l * N, h.dtype).reshape(B, ctx, R)
            latent_full = jnp.concatenate(
                [latent_ctx, jnp.swapaxes(lwl, 0, 1), latent_row[:, None]], axis=1
            )
            attn = jax.vmap(
                lambda qe, qr, lat, mb: _attend_latent(qe[None], qr[None], lat, mb[None], lp, c)[0]
            )(q_eff, q_rope, latent_full, mask_full)
            h = h + attn @ lp["wo"]
            x2 = rms_norm(h, lp["mlp_norm"], c.rms_norm_eps)
            h = h + _mlp(x2, lp, c, valid=active, experts=experts, layer=l)
            return h, latent_row

        h, lat_rows = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32), lat_win)
        )
        lat_win = lat_win.at[:, i].set(lat_rows)
        h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
        head = params.get("lm_head")
        logits = (h @ (head if head is not None else params["embed"].T)).astype(jnp.float32)
        key, sub = jax.random.split(key)
        nxt = sample_batch(logits, temps, top_ks, top_ps, sub).astype(jnp.int32)
        out = out.at[i].set(nxt)
        return (nxt, lat_win, out, key)

    # Window rows are in-flight REAL values; int8 caches quantize only at
    # the final fused scatter (k_cache.dtype would be int8 for QuantKv).
    lat_win0 = jnp.zeros((L, num_steps, B, R), dtype=params["embed"].dtype)
    out0 = jnp.zeros((num_steps, B), dtype=jnp.int32)
    _, lat_win, out, _ = lax.fori_loop(0, num_steps, body, (tokens, lat_win0, out0, rng_key))

    steps_i = jnp.arange(num_steps, dtype=jnp.int32)
    slots = jnp.where(active[None, :], positions[None, :] + steps_i[:, None], 0)  # [w, B]
    tgt_blocks = jnp.where(active[None, :], block_tables[jnp.arange(B)[None, :], slots // bs], 0)
    tgt_offs = slots % bs
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None, None], (L, num_steps, B))
    k_new = _scatter_kv(
        k_cache, layer_idx, tgt_blocks[None], tgt_offs[None], lat_win[:, :, :, None, :]
    )
    return out, k_new, v_cache
