"""Llama-family transformer: functional forward passes over a paged KV cache.

TPU-first design choices (vs the reference's CUDA engines):
- **Stacked layers + ``lax.scan``**: one compiled layer body regardless of
  depth — fast compiles, XLA-friendly.
- **Static shapes**: prefill runs on bucketed sequence lengths, decode on
  bucketed batch sizes; the scheduler picks the bucket, XLA caches one
  executable per bucket.
- **Paged KV**: block-table scatter on write, block gather on read. The
  gather-based attention keeps everything in pure XLA (works on CPU test
  meshes); the Pallas paged-attention kernel in
  ``dynamo_tpu.engine.attention`` replaces the gather on real TPUs.
- **bf16 weights/activations, f32 softmax + norms** (MXU-friendly).

Block 0 of the pool is reserved as a scratch sink: padded token positions
scatter there so no real block is corrupted (the allocator never hands out
block 0).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.kv_cache import (
    QuantKv,
    cache_rows,
    layer_flat,
    merge_heads,
    quantize_kv_rows,
    ragged_scatter_targets,
    split_heads,
)
from dynamo_tpu.engine.quant import dequant_layer
from dynamo_tpu.engine.sharding import HEADS, PAGES, kernel_shards, over_tp, step_mesh, tp_size

Params = Dict[str, jax.Array]

# The per-layer FFN weights; for a MoE FFN the expert stacks [L, E, D, F] /
# [L, E, F, D] (_split_expert_stacks).
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")

# decode_multi hoisted-gather budget: the once-per-window packed prefix
# buffer ([L, B, ctx, KVH, HD] × k+v) must stay well under spare HBM. Past
# this, the window falls back to per-step gathers.
_HOIST_GATHER_MAX_BYTES = 4 << 30


def _hoist_gather_budget() -> int:
    """Resolve the hoist cap at trace time. Env override first; otherwise a
    third of currently-free device memory (the buffer shares HBM with its
    own transient gather output), bounded by the static cap — a
    memory-tight config (e.g. int8 KV chosen for capacity, where the
    hoisted bf16 buffer is 2× the prefix's cache bytes) must fall back to
    per-step gathers rather than OOM a deployment that decoded fine
    before hoisting existed."""
    env = os.environ.get("DYNAMO_TPU_HOIST_GATHER_MAX_BYTES")
    if env is not None:
        return int(env)
    try:
        stats = jax.devices()[0].memory_stats() or {}
        free = int(stats.get("bytes_limit", 0)) - int(stats.get("bytes_in_use", 0))
        if free > 0:
            return min(_HOIST_GATHER_MAX_BYTES, free // 3)
    except Exception:
        pass
    return _HOIST_GATHER_MAX_BYTES


# Widest cache row XLA:TPU's gather of whole blocks is trusted with: at 4096
# lanes it first slices the POOL in halves of its lane axis, a copy of the
# pool in every layer (3-4.5 GB of temporaries at 177 blocks; compiled for a
# described v5e, PERF.md §6, PR 28). Past it one sequence's table is read by
# a dynamic slice a block.
_GATHER_MAX_LANES = 2048


def _gather_kv(flat, idx, dtype):
    """Gather KV rows through a block-table index; int8 caches dequantize on
    the way out (per-token-per-head symmetric scale).

    Dequant runs directly in the compute dtype — an f32 intermediate would
    double the materialized bytes (int8 codes are ≤7 bits of mantissa,
    safely inside bf16). Note: on current XLA:TPU the int8 gather itself
    does not run faster than bf16 (measured: parity at b8, slower at wide
    batch — the gather widens byte elements internally), so int8 KV is a
    CAPACITY feature (double the blocks per HBM byte — longer contexts,
    bigger batches before preemption), not a decode-latency one.

    ``flat`` is the layer-flat pool ``[L*N, BS, KVH*HD]``; the result is
    what ``idx`` selected, ``[*idx.shape, BS, KVH*HD]`` (int8:
    ``[*idx.shape, BS, KVH, HD]``), which every caller reshapes to
    ``[..., ctx, KVH, HD]`` — a reshape of the context gathered, never of
    the pool (the layout contract: ``KvCacheArrays``)."""
    if isinstance(flat, QuantKv):
        scale = flat.scale[idx]
        q = split_heads(flat.q[idx], scale.shape[-1])
        return q.astype(dtype) * scale[..., None].astype(dtype)
    if idx.ndim == 1 and flat.shape[-1] > _GATHER_MAX_LANES:
        return jnp.stack([lax.dynamic_index_in_dim(flat, idx[i], axis=0, keepdims=False) for i in range(idx.shape[0])])
    return flat[idx]


def _scatter_kv(cache, layer_idx, blocks, offs, rows):
    """Scatter fresh KV rows ``[..., KVH, HD]`` into the cache, merged into
    the pool's lane layout on the way in; int8 caches also quantize
    (requantization is stable to within one code step)."""
    if isinstance(cache, QuantKv):
        qk = quantize_kv_rows(rows)
        return QuantKv(
            cache.q.at[layer_idx, blocks, offs].set(qk.q),
            cache.scale.at[layer_idx, blocks, offs].set(qk.scale),
        )
    return cache.at[layer_idx, blocks, offs].set(merge_heads(rows))


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init weights (testing / benchmarking). HF checkpoint loading
    lives in ``dynamo_tpu.engine.weights``."""
    c = config
    if c.is_hybrid:
        from dynamo_tpu.engine.models import hybrid

        return hybrid.init_params(c, key, dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5 if len(shape) >= 2 else 0.02)
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    L = c.num_layers
    keys = jax.random.split(k_layers, 8)
    layers: Dict[str, jax.Array] = {
        "attn_norm": jnp.ones((L, c.hidden_size), dtype=dtype),
        "mlp_norm": jnp.ones((L, c.hidden_size), dtype=dtype),
        "wq": dense(keys[0], (L, c.hidden_size, c.q_size)),
        "wk": dense(keys[1], (L, c.hidden_size, c.kv_size)),
        "wv": dense(keys[2], (L, c.hidden_size, c.kv_size)),
        "wo": dense(keys[3], (L, c.q_size, c.hidden_size)),
    }
    if c.num_experts == 0:
        layers.update(
            w_gate=dense(keys[4], (L, c.hidden_size, c.intermediate_size)),
            w_up=dense(keys[5], (L, c.hidden_size, c.intermediate_size)),
            w_down=dense(keys[6], (L, c.intermediate_size, c.hidden_size)),
        )
    else:
        E = c.num_experts
        layers.update(
            router=dense(keys[7], (L, c.hidden_size, E)),
            w_gate=dense(keys[4], (L, E, c.hidden_size, c.intermediate_size)),
            w_up=dense(keys[5], (L, E, c.hidden_size, c.intermediate_size)),
            w_down=dense(keys[6], (L, E, c.intermediate_size, c.hidden_size)),
        )
    params: Params = {
        "embed": dense(k_embed, (c.vocab_size, c.hidden_size), scale=0.02),
        "final_norm": jnp.ones((c.hidden_size,), dtype=dtype),
        "layers": layers,
    }
    if c.norm_unit_offset:
        # Norm weights are stored around zero and applied as 1 + g.
        for name in ("attn_norm", "mlp_norm"):
            layers[name] = jnp.zeros_like(layers[name])
        params["final_norm"] = jnp.zeros_like(params["final_norm"])
    if c.is_eva:
        # Pooling queries of the chunk summaries, one per head and layer
        # (eva_roll): mu weights the key pooling, phi the value pooling.
        k_mu, k_phi = jax.random.split(jax.random.fold_in(k_layers, 1))
        layers["eva_mu"] = dense(k_mu, (L, c.num_kv_heads, c.head_dim), scale=1.0)
        layers["eva_phi"] = dense(k_phi, (L, c.num_kv_heads, c.head_dim), scale=1.0)
    if not c.tie_word_embeddings:
        params["lm_head"] = dense(k_head, (c.hidden_size, c.vocab_size * c.num_pred_heads), scale=0.02)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float, unit_offset: bool = False, out_dtype=None) -> jax.Array:
    """RMS norm in float32; ``unit_offset`` multiplies by ``1 + weight``;
    the result is ``out_dtype`` (default: the input's)."""
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    return (norm * (1.0 + w if unit_offset else w)).astype(x.dtype if out_dtype is None else out_dtype)


def _norm(c: ModelConfig, x: jax.Array, weight: jax.Array, wdtype) -> jax.Array:
    """The configuration's norm of the residual stream, as a matmul's input:
    in the compute dtype ``wdtype`` even where the stream is float32."""
    return rms_norm(x, weight, c.rms_norm_eps, c.norm_unit_offset, wdtype)


def _embed_rows(c: ModelConfig, params: Params, tokens: jax.Array):
    """Embedding rows that open the residual stream, and the compute dtype
    ``wdtype`` of every matmul input after them. The stream itself is float32
    where the configuration says so (each residual add then promotes)."""
    h = params["embed"].at[tokens].get(mode="clip")
    return (h.astype(jnp.float32) if c.residual_fp32 else h), h.dtype


def _logits(c: ModelConfig, params: Params, h: jax.Array, wdtype) -> jax.Array:
    """Final norm and head of the picked rows -> float32 next-token logits.
    Of a head with several prediction heads only the first ``vocab_size``
    columns are multiplied; with a float32 residual the product also leaves
    the MXU in float32."""
    x = _norm(c, h, params["final_norm"], wdtype)
    head = params.get("lm_head")
    head = head if head is not None else params["embed"].T
    if c.num_pred_heads > 1:
        head = head[:, : c.vocab_size]
    if c.residual_fp32:
        return jnp.dot(x, head, preferred_element_type=jnp.float32)
    return (x @ head).astype(jnp.float32)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., T, heads, head_dim]; positions: [..., T]."""
    freqs = rope_frequencies(x.shape[-1], theta)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def project_heads(x: jax.Array, w: jax.Array, heads: int, positions: Optional[jax.Array] = None, theta: float = 0.0) -> jax.Array:
    """``x @ w`` cut into heads, ``[..., heads, w.shape[-1] // heads]``, and
    rotated where ``positions`` (``x``'s shape less its last axis) are given.
    Every q and k projection of a step program goes through here.

    The barrier holds the product as the dot writes it, row-major
    ``[rows, heads * head_dim]`` like ``wv``'s, ``wo``'s and the FFN's, so
    that what gets re-laid to heads is the activation (at most a chunk's
    rows). Without it XLA:TPU carries the head-major layout that rope and the
    attention kernels want back through the reshape into the dot, writes the
    product head by head, and for that re-lays the WEIGHT: a window's program
    held ``copy.69 = bf16[L,4096,4096]{1,2,0} copy(p.layers.wq)`` (both whole
    stacks transposed once a dispatch) and a mixed step
    ``copy.196 = bf16[1,4096,4096]{1,2,0} copy(constant_dynamic-slice_fusion.5)``
    (a layer's slice of ``wq``) inside its layer scan: 0.64 s of copies and
    1.07 s of slices in 11 s of device time on ``evabyte-d16.doc-bytes``
    (ledger, PR 39; PERF.md section 6, PR 40). A barrier after the reshape does
    not do: it is the reshape of the dot's own result that carries the layout.
    ``tests/test_tpu_compile.py::test_no_step_program_re_lays_a_weight`` reads
    the compiled programs.

    On a TPU the product stays float32 from the dot to the end of the rotation
    and is rounded to ``x.dtype`` once, there: what the programs computed there
    before the barrier, when XLA fused the dot with the rotation's widening
    and never rounded between them (``convert_bitcast_fusion =
    f32[B,1,32,128]``; excess precision it is allowed). A barrier on the bf16
    product forces that rounding: the output check's ``rel_err`` on
    ``evabyte-d16.doc-bytes`` then read 0.0092-0.0096 where it had read
    0.0089-0.0092 on the same four seeds; so it reads 0.0089-0.0092, and the
    two ``llama`` cells read the parent's to the last digit. Off the TPU XLA
    did round there, and so does this: the CPU's results
    (``tests/benchmark/frozen_parent.json``) stay what they were."""
    wide = jnp.float32 if _on_tpu() else x.dtype
    y = lax.optimization_barrier(jnp.dot(x, w, preferred_element_type=wide))
    y = y.reshape(*x.shape[:-1], heads, w.shape[-1] // heads)
    if positions is not None:
        y = apply_rope(y, positions, theta)
    return y.astype(x.dtype)


def _route(x: jax.Array, lp: Dict[str, jax.Array], K: int):
    """Top-k routing: (weights [T,K] f32 softmax over the chosen experts,
    expert ids [T,K] i32)."""
    router_logits = (x @ lp["router"]).astype(jnp.float32)  # [T, E]
    top_vals, top_idx = lax.top_k(router_logits, K)
    return jax.nn.softmax(top_vals, axis=-1), top_idx


def _moe_dense(x: jax.Array, lp: Dict[str, jax.Array], config: ModelConfig) -> jax.Array:
    """Every expert computes every token; router weights combine. Exact but
    compute inflates ×E/K — the tiny-model / debugging fallback."""
    T = x.shape[0]
    E, K = config.num_experts, config.num_experts_per_tok
    weights, top_idx = _route(x, lp, K)
    weights = weights.astype(x.dtype)
    combine = jnp.zeros((T, E), dtype=x.dtype).at[jnp.arange(T)[:, None], top_idx].set(weights)
    g = jnp.einsum("td,edf->tef", x, lp["w_gate"])
    u = jnp.einsum("td,edf->tef", x, lp["w_up"])
    h = jax.nn.silu(g) * u
    out = jnp.einsum("tef,efd->ted", h, lp["w_down"])
    return jnp.einsum("ted,te->td", out, combine)


def _split_expert_stacks(c: ModelConfig, layers: Dict[str, jax.Array]):
    """What a layer scan takes as ``xs`` and what it closes over: ``(scanned,
    experts)``.

    For a MoE FFN under the grouped-GEMM dispatch, ``experts`` holds the three
    expert stacks as ``[L*E, D, F]`` / ``[L*E, F, D]`` views of the stored
    ``[L, E, ...]`` arrays (a merge of leading dimensions: no element moves)
    and ``scanned`` is the tree without them. A scan that slices a stack per
    layer hands ``ragged_dot`` a dynamic slice, which XLA:TPU does not fuse
    into it: every layer of every step then copies its ``[E, D, F]`` out of
    the stack before reading it (12.8 ms a Mixtral layer on v5e against 4.4;
    PERF.md §6, PR 29). ``_mlp(..., experts=experts, layer=l)`` reads layer
    ``l``'s experts where they lie instead.

    A dense FFN and the "dense"/"capacity" dispatches are returned as they
    came, ``experts`` None: the scan slices per layer as before (int8 storage
    does not cover expert stacks: ``ModelConfig`` refuses it)."""
    if c.num_experts == 0 or c.moe_dispatch not in ("auto", "ragged"):
        return layers, None
    scanned = {k: v for k, v in layers.items() if k not in _EXPERT_STACKS}
    experts = {k: layers[k].reshape((-1,) + layers[k].shape[2:]) for k in _EXPERT_STACKS}
    return scanned, experts


def _moe_ragged(
    x: jax.Array,
    lp: Dict[str, jax.Array],
    config: ModelConfig,
    valid: Optional[jax.Array] = None,
    experts: Optional[Dict[str, jax.Array]] = None,
    layer=None,
) -> jax.Array:
    """Sparse dispatch via grouped GEMM (``lax.ragged_dot``): sort the T·K
    (token, expert) assignments by expert, run one ragged matmul per
    projection over the expert-contiguous rows, and scatter-add the weighted
    outputs back. Exact (no token drops) and per-token expert FLOPs scale
    with K, not E — the MegaBlocks formulation in native XLA. Best on a
    single shard or tp-sharded weights (the group axis cannot be partitioned
    over ``ep``; use "capacity" dispatch there).

    The GEMMs' ``rhs`` is ``experts`` (``_split_expert_stacks``): the whole
    ``[L*E, D, F]`` stack, with ``L*E`` group sizes that are zero outside
    layer ``layer``'s ``[l*E, (l+1)*E)``. XLA:TPU's ``ragged-dot`` reads the
    weights of non-empty groups only (measured: PERF.md §6, PR 29), so this
    reads exactly the experts layer ``l`` visits, in place. Without
    ``experts`` the rhs is ``lp``'s own ``[E, D, F]`` (a lone layer).

    ``valid`` masks padded rows (inactive decode lanes / prefill padding):
    they are folded into this layer's expert 0 — group ``l*E``, not group 0,
    which is another layer's expert — (finite compute, bounded by bucket
    padding) and combined with weight 0."""
    E, K = config.num_experts, config.num_experts_per_tok
    weights, top_idx = _route(x, lp, K)
    flat_e = top_idx.reshape(-1)  # [T*K]
    wflat = weights.reshape(-1)
    if valid is not None:
        vflat = jnp.repeat(valid, K)
        flat_e = jnp.where(vflat, flat_e, 0)
        wflat = jnp.where(vflat, wflat, 0.0)
    order = jnp.argsort(flat_e)  # stable: expert-major, token order within
    tok = order // K  # source token per sorted row
    xs = x[tok]  # [T*K, D]
    if experts is None:
        experts, first = lp, 0
    else:
        first = layer * E  # this layer's expert 0 among the stack's groups
    group_sizes = jnp.bincount(flat_e + first, length=experts["w_gate"].shape[0])  # [L*E] or [E]
    g = lax.ragged_dot(xs, experts["w_gate"], group_sizes)
    u = lax.ragged_dot(xs, experts["w_up"], group_sizes)
    h = jax.nn.silu(g) * u
    y = lax.ragged_dot(h, experts["w_down"], group_sizes)  # [T*K, D]
    w_sorted = wflat[order].astype(x.dtype)
    return jnp.zeros_like(x).at[tok].add(y * w_sorted[:, None])


_GMM_RHS_TILE_BYTES = 6 * 2**20  # the largest tile of an expert's weights ``_held_dot`` asks of gmm (4096 x 768 bf16)


def _held_dot(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """The grouped product of ``_moe_held``: ``lhs [M, K]`` rows sorted by
    group against ``rhs [G, K, N]``. On a TPU the megablox ``gmm`` kernel at
    row tiles of 128 and whole-K, whole-N tiles: at narrow experts (768 wide)
    it reads 1.03 ms a layer at 32 rows where ``lax.ragged_dot`` reads 1.46,
    and 1.72 against 2.80 at a mixed step's rows (tools/ssm_step_bench.py;
    PERF.md §6, PR 32). ``_moe_ragged`` (every expert held, Mixtral's
    14336-wide experts) still gives ``lax.ragged_dot`` its groups although
    ``gmm`` with wide tiles read 3.78 ms a layer against 4.40 there too
    (tools/moe_gemm_bench.py, PR 29): its cell could not show the gain end to
    end, so nobody shipped it; one path for both is ROADMAP S18. Rows past the
    last group belong to no tile and come back as they lie. Elsewhere
    ``lax.ragged_dot``."""
    if not _on_tpu():
        return lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k, n = lhs.shape[0], rhs.shape[1], rhs.shape[2]
    tm = min(128, -(-m // 8) * 8)
    if m % tm:
        lhs = jnp.concatenate([lhs, jnp.zeros((-m % tm, k), lhs.dtype)])
    tk, tn = min(4096, k), min(4096, n)
    while tk * tn * rhs.dtype.itemsize > _GMM_RHS_TILE_BYTES and tn % 256 == 0:
        tn //= 2  # a wide expert (2048 x 2048): two fetched tiles of the weights have to fit the kernel's fast memory
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype, tiling=(tm, tk, tn))[:m]


def _moe_held(
    x: jax.Array,
    lp: Dict[str, jax.Array],
    config: ModelConfig,
    valid: Optional[jax.Array] = None,
    experts: Optional[Dict[str, jax.Array]] = None,
    layer=None,
    route=None,
):
    """``_moe_ragged`` for an expert layer that holds a share
    (``ModelConfig.num_experts_held``): the router is ``num_experts`` wide and
    the gates are the softmax over all K chosen logits, as published; the
    stacks hold experts ``[first_expert_held, first_expert_held + E_held)``.
    Assignments to held experts sort to the front, expert-major, and make the
    groups of the three grouped products (``_held_dot``); those to absent
    experts (the other chip's) and those of padded rows sort past them into no
    group and add nothing. ``experts`` is the
    ``[L*E_held, D, F]`` view of ``_split_expert_stacks`` and ``layer`` the
    layer's index in it. ``route(x, lp) -> (weights [T, K], ids [T, K])`` is
    the router (default: ``_route`` at ``num_experts_per_tok``); an id past
    the experts (a skip choice) is an absent expert's: it adds nothing.

    Returns ``(out, held, visited)``: the held share's sum per token, the
    assignments that fell on held experts and the experts that got any (i32
    scalars, for the step log)."""
    E_held = config.experts_held
    weights, top_idx = _route(x, lp, config.num_experts_per_tok) if route is None else route(x, lp)
    K = top_idx.shape[-1]
    local = top_idx.reshape(-1) - config.first_expert_held  # [T*K]
    held = (local >= 0) & (local < E_held)
    if valid is not None:
        held = held & jnp.repeat(valid, K)
    order = jnp.argsort(jnp.where(held, local, E_held))  # stable: held first, expert-major
    tok = order // K
    if experts is None:
        experts, first = lp, 0
    else:
        first = layer * E_held
    groups = experts["w_gate"].shape[0]
    group_sizes = jnp.zeros((groups,), jnp.int32).at[jnp.where(held, local + first, groups)].add(1, mode="drop")
    with jax.named_scope("moe_held"):
        xs = x[tok]
        g = _held_dot(xs, experts["w_gate"], group_sizes)
        u = _held_dot(xs, experts["w_up"], group_sizes)
        y = _held_dot(jax.nn.silu(g) * u, experts["w_down"], group_sizes)  # [T*K, D]
    # Rows past the last group belong to no GEMM: whatever stands there is dropped, not scaled by zero.
    held_sorted = held[order]
    y = jnp.where(held_sorted[:, None], y * weights.reshape(-1)[order].astype(x.dtype)[:, None], 0)
    out = jnp.zeros_like(x).at[tok].add(y)
    return out, jnp.sum(held).astype(jnp.int32), jnp.sum(group_sizes > 0).astype(jnp.int32)


def _moe_capacity(
    x: jax.Array, lp: Dict[str, jax.Array], config: ModelConfig, valid: Optional[jax.Array] = None
) -> jax.Array:
    """GShard-style capacity-factor dispatch: each expert owns C static
    slots (C = T·K/E · capacity_factor); dispatch/combine are one-hot
    einsums over [E, C, T], so GSPMD partitions the expert axis over the
    ``ep`` mesh and the FFN hidden dim over ``tp`` with a single psum
    combine — the wide-EP serving path. Earlier tokens win slots; a token
    overflowing every chosen expert's capacity contributes only its residual
    (raise ``moe_capacity_factor`` if drop counters show pressure).

    ``valid`` masks padded rows so inactive decode lanes cannot steal
    capacity slots from live tokens (they are excluded from the slot count
    and dispatched nowhere).

    Cost note: the dispatch/combine einsums are O(E·C·T·D) = O(cf·K·T²·D) —
    quadratic in T. Relative to the expert GEMMs (O(cf·K·T·D·F)) that is
    ~T/(3F): negligible for decode batches, ~5% at T=2048/F=14336, growing
    linearly with prefill chunk length — bound the chunk size fed through
    this path (the scheduler's prefill buckets already do)."""
    import math

    T = x.shape[0]
    E, K = config.num_experts, config.num_experts_per_tok
    C = max(1, min(T, math.ceil(T * K * config.moe_capacity_factor / E)))
    weights, top_idx = _route(x, lp, K)
    flat_e = top_idx.reshape(-1)  # [T*K]
    tok = jnp.arange(T * K, dtype=jnp.int32) // K
    # Slot of each assignment within its expert's queue (t-major priority).
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [T*K, E]
    if valid is not None:
        # Invalid rows occupy no slots and are never dispatched.
        onehot = onehot * jnp.repeat(valid, K).astype(jnp.int32)[:, None]
    slot = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C
    live = jnp.ones_like(keep) if valid is None else jnp.repeat(valid, K)
    keep = keep & live
    # Capacity-drop accounting: live assignments that lost the slot race
    # (their token contributes only its residual). Exported per step via
    # ForwardPassMetrics → Prometheus when moe_stats is requested (ref:
    # wide-EP observability, SURVEY.md §2e).
    dropped = jnp.sum(live & ~keep).astype(jnp.int32)
    slot_c = jnp.clip(slot, 0, C - 1)
    # (e, slot) pairs are unique among kept rows (cumsum), so .add == .set;
    # dropped rows add 0.
    disp = jnp.zeros((E, C, T), dtype=x.dtype).at[flat_e, slot_c, tok].add(keep.astype(x.dtype))
    comb = jnp.zeros((E, C, T), dtype=jnp.float32).at[flat_e, slot_c, tok].add(
        jnp.where(keep, weights.reshape(-1), 0.0)
    )
    xe = jnp.einsum("ect,td->ecd", disp, x)  # gather tokens into slots
    g = jnp.einsum("ecd,edf->ecf", xe, lp["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, lp["w_up"])
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("ecf,efd->ecd", h, lp["w_down"])
    out = jnp.einsum("ecd,ect->td", ye.astype(jnp.float32), comb).astype(x.dtype)
    return out, dropped


def _mlp(
    x: jax.Array,
    lp: Dict[str, jax.Array],
    config: ModelConfig,
    valid: Optional[jax.Array] = None,
    stats: bool = False,
    experts: Optional[Dict[str, jax.Array]] = None,
    layer=None,
):
    """Feed-forward block: dense SwiGLU, or MoE when config.num_experts > 0.

    MoE dispatch is selected by ``config.moe_dispatch`` (see config.py):
    "ragged" (exact grouped GEMM, K-scaling FLOPs) by default, "capacity"
    (GShard einsum dispatch over the ``ep`` axis) for wide-EP meshes,
    "dense" as the exhaustive fallback. "auto" resolves via
    ``resolve_moe_dispatch`` wherever the mesh is known (Scheduler,
    pipelined decode); direct model calls default to "ragged". The reference
    only *configures* wide-EP in its engines (SURVEY.md §2e,
    trtllm_utils.py:37); here the dispatch kernel is native.

    ``valid`` marks live rows (decode ``active`` lanes / prefill valid
    tokens); sparse dispatch excludes dead rows so they cannot consume
    expert capacity meant for live tokens.

    ``experts`` and ``layer`` are what a layer scan got from
    ``_split_expert_stacks`` and its layer index: with them the grouped
    GEMMs read layer ``layer``'s experts out of the whole ``[L*E, D, F]``
    stacks, which ``lp`` then does not hold. Without them (a dense FFN,
    "dense"/"capacity" dispatch, a lone layer's ``lp``) the FFN weights are
    ``lp``'s own.

    With ``stats=True`` returns ``(out, dropped i32)`` — the number of live
    (token, expert) assignments dropped by capacity pressure this call
    (always 0 for exact dispatch modes)."""
    if config.num_experts == 0:
        out = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
        return (out, jnp.int32(0)) if stats else out
    mode = config.moe_dispatch
    if mode == "auto":
        mode = "ragged"
    if mode == "dense":
        out = _moe_dense(x, lp, config)
        return (out, jnp.int32(0)) if stats else out
    if mode == "ragged":
        out = _moe_ragged(x, lp, config, valid, experts, layer)
        return (out, jnp.int32(0)) if stats else out
    out, dropped = _moe_capacity(x, lp, config, valid)
    return (out, dropped) if stats else out


def _refuse_eva(c: ModelConfig, what: str) -> None:
    """Programs that equate a token's position with its cache row are not
    built for ``attention_kind='eva'``: refuse, never serve wrong rows."""
    if c.is_eva:
        raise NotImplementedError(f"{what} is not built for attention_kind='eva' (model {c.name!r})")
    c.refuse_for_layer_types(what)  # nor for a model that holds recurrent state beside its table


def _on_tpu() -> bool:
    """THE place that decides whether Pallas kernels run compiled (TPU) or
    interpreted, and what "auto" resolves to. A backend that fails to
    initialize raises here — it is not "not a TPU"."""
    return jax.default_backend() == "tpu"


_warned_paged_int8 = False
_warned_unpartitioned = False


def resolve_attention_impl(c: ModelConfig, k_cache) -> str:
    """Resolve ``ModelConfig.attention_impl`` against the backend and the
    cache dtype → one of ``"gather" | "paged" | "megakernel"``.

    - ``"auto"`` flips to the ragged megakernel on TPU (where its
      one-launch-per-layer amortization wins — see the attention_impl
      docstring for the measured record) and stays on the XLA gather off-
      TPU (interpreted Pallas is test-only).
    - ``"paged"`` (the r5 per-piece kernel) has no int8 path; int8-KV
      deployments degrade to the gather with a logged warning instead of
      the former hard ValueError — the megakernel is the int8-capable
      fused path.
    - Under a ``tp`` mesh the kernels run per shard over the local KV heads
      (``sharding.over_tp``). Where the KV heads do not divide by ``tp`` the
      cache replicates and the kernels cannot partition: the XLA gather
      serves that mesh (``warn_attention_impl_degrade`` says so once).
    """
    impl = c.attention_impl
    if c.is_latent:
        return "gather"  # the latent kinds attend through XLA's gather of the table (models/latent.py): no kernel takes their rows
    if impl == "auto":
        impl = "megakernel" if _on_tpu() else "gather"
    if impl != "gather" and kernel_shards(c.num_kv_heads) == 0:
        impl = "gather"
    if impl == "paged" and isinstance(k_cache, QuantKv):
        # Pure resolution only: this runs inside traced bodies
        # (_use_paged_decode / _use_megakernel), where host-side logging is
        # a trace-time effect. warn_attention_impl_degrade() carries the
        # operator-facing warning from the scheduler's init path.
        impl = "gather"
    return impl


def resolve_prefill_impl(c: ModelConfig) -> str:
    """Resolve ``ModelConfig.prefill_impl`` → ``"flash" | "xla"``: "auto" is
    the Pallas flash kernel on TPU; a ``tp`` mesh whose KV heads do not
    divide takes the XLA path (see ``resolve_attention_impl``)."""
    impl = c.prefill_impl
    if c.is_latent:
        return "xla"
    if impl == "auto":
        impl = "flash" if _on_tpu() else "xla"
    if impl == "flash" and kernel_shards(c.num_kv_heads) == 0:
        impl = "xla"
    return impl


def warn_attention_impl_degrade(c: ModelConfig, k_cache) -> None:
    """Host-side companion to ``resolve_attention_impl``: log the paged+int8
    and the unpartitionable-mesh degrades once, from setup code (the
    scheduler's __init__), never from a jit-reachable body."""
    global _warned_paged_int8, _warned_unpartitioned
    if (
        kernel_shards(c.num_kv_heads) == 0
        and (c.attention_impl, c.prefill_impl) != ("gather", "xla")
        and not _warned_unpartitioned
    ):
        _warned_unpartitioned = True
        import logging

        logging.getLogger(__name__).warning(
            "num_kv_heads=%d does not divide by tp=%d: the Pallas attention "
            "kernels cannot partition over this mesh — attention runs on the "
            "XLA gather and XLA prefill paths.",
            c.num_kv_heads, tp_size(step_mesh()),
        )
    if (
        c.attention_impl == "paged"
        and isinstance(k_cache, QuantKv)
        and not _warned_paged_int8
    ):
        _warned_paged_int8 = True
        import logging

        logging.getLogger(__name__).warning(
            "attention_impl='paged' has no int8-KV path — degrading to "
            "the XLA gather for this deployment. Use "
            "attention_impl='megakernel' for the fused int8 "
            "dequant-in-VMEM path."
        )


def _use_paged_decode(c: ModelConfig, k_cache) -> bool:
    """The length-1 rows' prefix through the r5 per-piece Pallas paged kernel
    (attention/decode.py) — still explicit opt-in only; superseded by the
    ragged megakernel for the fused path. int8 caches degrade to gather
    (resolve_attention_impl). A chunk beside them walks tiles
    (``chunk_walks_tiles``)."""
    return resolve_attention_impl(c, k_cache) == "paged"


def _use_megakernel(c: ModelConfig, k_cache) -> bool:
    """The length-1 rows through the ragged paged-attention megakernel
    (attention/megakernel.py): a launch a layer over the list of their live
    pages, no gathered prefix copy, no step for a dead row or slot.
    Auto-selected on TPU."""
    return resolve_attention_impl(c, k_cache) == "megakernel"


def chunk_walks_tiles(c: ModelConfig, k_cache) -> bool:
    """THE rule for a wide row (a prefill chunk, in ``prefill``,
    ``mixed_step`` and hybrid.py's attention mixer): it meets its paged prefix
    and its own keys in one launch a layer of the megakernel's tile walk
    (``_mega_attend_rows`` without ``work``) wherever a Pallas kernel serves
    the pool at all — ``resolve_attention_impl`` says ``"megakernel"`` or
    ``"paged"``, which differ in the length-1 rows' kernel alone. Only
    ``"gather"`` (``auto`` off the TPU, a mesh whose KV heads do not divide,
    an int8 pool under ``paged``, a latent kind) takes attention/ragged.py's
    gathered prefix."""
    return resolve_attention_impl(c, k_cache) != "gather"


def _chunk_tile(c: ModelConfig, k_cache, num_queries: int, dtype) -> int:
    """The megakernel's tile for a chunk of ``num_queries`` ``dtype`` queries
    (megakernel.chunk_tile at this step's shard of the heads)."""
    from dynamo_tpu.engine.attention.megakernel import chunk_tile

    tp = max(kernel_shards(c.num_kv_heads), 1)
    return chunk_tile(
        num_queries, c.num_heads // tp, c.num_kv_heads // tp, c.head_dim, c.block_size,
        q_bytes=jnp.dtype(dtype).itemsize,
        kv_bytes=jnp.dtype(k_cache.dtype).itemsize,
    )


def chunk_attn_path(c: ModelConfig, k_cache, num_queries: int, dtype) -> str:
    """How a chunk of ``num_queries`` meets its keys in ``prefill`` and
    ``mixed_step`` as they trace now: ``tile<TQ>`` (the ragged megakernel's
    (tile, page) walk, ``chunk_walks_tiles``) or ``gather``. For the step log."""
    return f"tile{_chunk_tile(c, k_cache, num_queries, dtype)}" if chunk_walks_tiles(c, k_cache) else "gather"


def rows_pages_per_step(c: ModelConfig, k_cache, num_slots: int) -> int:
    """Table slots a step of the length-1 rows' launch takes over ``k_cache``
    (any layout of the pool: its last axes are a page) and a table
    ``num_slots`` wide: what ``ragged_paged_attention`` reads off this
    step's shard of a page (megakernel.pages_per_step)."""
    from dynamo_tpu.engine.attention.megakernel import pages_per_step

    tp = max(kernel_shards(c.num_kv_heads), 1)
    return pages_per_step(
        k_cache.shape[-2], k_cache.shape[-1] // tp, jnp.dtype(k_cache.dtype).itemsize, num_slots
    )


def _mega_rows_work(c: ModelConfig, k_cache, prefix_lens: jax.Array, active: jax.Array, num_slots: int) -> jax.Array:
    """The work list of a step's length-1 rows (megakernel.build_work), at
    the pages a step their launch takes: built once a step program, handed to
    every layer's ``_mega_attend_rows``."""
    from dynamo_tpu.engine.attention.megakernel import build_work

    return build_work(prefix_lens, active, num_slots, c.block_size, rows_pages_per_step(c, k_cache, num_slots))


def _mega_attend_rows(
    c: ModelConfig,
    q: jax.Array,  # [NQ, H, HD]
    k_extra: jax.Array,  # [CK, KVH, HD]
    v_extra: jax.Array,
    k_flat,  # [L*N, BS, KVH*HD] layer-flat pages (QuantKv ok)
    v_flat,
    tables: jax.Array,  # [R, W] layer-offset page tables
    meta: jax.Array,  # [5, NQ] megakernel.build_meta
    work: Optional[jax.Array] = None,  # length-1 rows: their megakernel.build_work, built once a step
) -> jax.Array:
    """One fused ragged-attention launch for a step's rows — per tp shard
    over its local heads when the step runs under a mesh. Without ``work``
    the queries are one wide row (a prefill chunk), walked by tiles."""
    from dynamo_tpu.engine.attention.megakernel import ragged_paged_attention

    args = (q, k_extra, v_extra, k_flat, v_flat, tables, meta) + (() if work is None else (work,))
    attend = over_tp(
        ragged_paged_attention, c.num_kv_heads,
        (HEADS, HEADS, HEADS, PAGES, PAGES) + (P(),) * (len(args) - 5), HEADS,
        block_size=c.block_size, interpret=not _on_tpu(),
        tile=_chunk_tile(c, k_flat, q.shape[0], q.dtype) if work is None else 1,
    )
    return attend(*args)


def _paged_prefix_partials(c: ModelConfig, q, k_flat, v_flat, tables_l, lengths):
    """Kernel-backed prefix piece in the ``_attend_piece`` partial layout."""
    from dynamo_tpu.engine.attention.decode import paged_decode_partials

    partials = over_tp(
        paged_decode_partials, c.num_kv_heads,
        (HEADS, PAGES, PAGES, P(), P()), (HEADS, HEADS, P(None, "tp", None, None)),
        block_size=c.block_size, interpret=not _on_tpu(),
    )
    return partials(q, k_flat, v_flat, tables_l, lengths)


def _attend_piece(qg, kp, vp, maskp, scale):
    """Partial decode attention over one KV piece → (m, l, acc) online-
    softmax state. qg [B,KVH,G,hd]; kp/vp [B,S,KVH,hd]; maskp [B,S].
    Shared by both decode backends: the Pallas paged kernel produces the
    same partials for the cached prefix, so the pieces merge identically."""
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kp).astype(jnp.float32) * scale
    s = jnp.where(maskp[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)  # [B,KVH,G]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgs,bskd->bkgd", p.astype(vp.dtype), vp).astype(jnp.float32)
    return m, l, acc


def _merge_pieces(m1, l1, acc1, m2, l2, acc2) -> jax.Array:
    """Close the online softmax across two attention pieces → [B,KVH,G,hd]
    f32 (caller casts). All-masked pieces (m = -inf, l = 0) drop out."""
    m_t = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m_t)
    a2 = jnp.exp(m2 - m_t)
    l_t = l1 * a1 + l2 * a2
    acc = acc1 * a1[..., None] + acc2 * a2[..., None]
    return acc / jnp.maximum(l_t, 1e-30)[..., None]


def _attend(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, config: ModelConfig) -> jax.Array:
    """q: [T, H, hd]; k/v: [S, KVH, hd]; mask: [T, S] bool → [T, H, hd].

    Grouped-query form: query heads are folded into (kv_head, group) so the
    KV tensors are used as-is — no ``jnp.repeat`` materialization (which
    would multiply HBM traffic by the group factor every layer)."""
    T = q.shape[0]
    kvh, hd = config.num_kv_heads, config.head_dim
    groups = config.num_heads // kvh
    qg = q.reshape(T, kvh, groups, hd)
    scale = config.head_dim ** -0.5
    scores = jnp.einsum("tkgd,skd->ktgs", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[None, :, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("ktgs,skd->tkgd", probs, v)
    return out.reshape(T, config.num_heads, hd)


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    tokens: jax.Array,  # [T] bucket-padded token ids
    valid_len: jax.Array,  # scalar: actual new tokens
    cache_len: jax.Array,  # scalar: tokens already in the block table (prefix reuse / chunked prefill)
    block_table: jax.Array,  # [W] block ids (0 = scratch); W bucketed by the caller
    all_logits: bool = False,  # static: return logits for every position [T, V]
    use_flash: bool = False,  # static: Pallas flash kernel for chunk attention
    has_prefix: bool = True,  # static: False ⇒ cache_len == 0, skip the prefix piece
    mm_feats: Optional[jax.Array] = None,  # [F, D] multimodal feature rows
    mm_len: Optional[jax.Array] = None,  # scalar i32: valid feature rows
    moe_stats: bool = False,  # static: also return {"moe_dropped", "moe_assignments"}
) -> Tuple[jax.Array, ...]:
    """One prefill (or prefill chunk). Returns (last_logits [V], k_cache,
    v_cache) — or ([T, V] logits with ``all_logits=True``, the target-model
    verification pass for speculative decoding; spec_decode.py).

    Wherever a kernel serves the pool the chunk walks the megakernel's tiles
    (``chunk_walks_tiles``) and neither ``use_flash`` nor ``has_prefix`` is
    read. Under the gather, with ``use_flash`` the chunk's causal
    self-attention runs in the Pallas flash kernel (attention/prefill.py —
    scores never leave VMEM) and the cached-prefix piece (absent for fresh
    prefills: ``has_prefix=False``) is an online-softmax partial merged
    outside the kernel; the XLA path (use_flash=False) materializes the full
    [T, ctx+T] mask — CPU meshes / debugging."""
    c = config
    bs = c.block_size
    T = tokens.shape[0]
    ctx = block_table.shape[0] * bs

    h, wdtype = _embed_rows(c, params, tokens)  # [T, D]
    positions = cache_len + jnp.arange(T, dtype=jnp.int32)
    # Rope turns by position; the cache is addressed by row (cache_rows). A
    # chunk never straddles a window boundary (the scheduler cuts it there),
    # so its rows are consecutive and the rows below its first are its prefix.
    kv_rows = cache_rows(c, positions)
    prefix_rows = cache_rows(c, cache_len)
    valid_q = jnp.arange(T, dtype=jnp.int32) < valid_len
    if mm_feats is not None:
        # Multimodal early fusion: positions [0, mm_len) are image-feature
        # rows (vision-prefix); override their token embeddings with the
        # encoder's projected features (ref role: trtllm encode_helper.py —
        # the encode worker hands features to prefill).
        inject = (positions < mm_len) & valid_q
        rows = mm_feats.at[jnp.clip(positions, 0, mm_feats.shape[0] - 1)].get(mode="clip")
        h = jnp.where(inject[:, None], rows.astype(wdtype), h)

    # Scatter targets for the new tokens; padded positions sink to block 0.
    tgt_blocks, tgt_offs = ragged_scatter_targets(block_table, kv_rows, valid_q, bs)

    # The cache is READ-ONLY inside the layer scan (slices ride the scan xs);
    # each layer's fresh chunk K/V is attended in-register and stacked into
    # the scan ys, then ONE fused scatter writes all layers afterwards. A
    # scatter inside the carry forced XLA into a full cache copy per layer
    # (~5 ms/step at 1B/b8 on v5e — measured); this formulation keeps the
    # cache bytes touched proportional to the tokens written.
    interp = not _on_tpu()
    kvh = c.num_kv_heads

    # Layer-flat cache view: gathering from [L*N, ...] with layer-offset
    # tables avoids the scan's per-layer dynamic-slice of the cache, which
    # XLA materializes as a full layer-cache copy per iteration (measured:
    # the dominant decode-attention cost at 1B/b32 on v5e). The reshape is
    # layout-free ([L, N] row-major ≡ [L*N]); block 0 of every layer stays a
    # scratch sink because offset tables map 0 → l*N, layer l's own block 0.
    L = c.num_layers
    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    v_flat = layer_flat(v_cache)

    walks_tiles = chunk_walks_tiles(c, k_cache)
    if walks_tiles:
        # The prefill chunk is one wide megakernel row, walked by tiles of
        # its queries: causal fresh chunk + paged prefix in ONE launch per
        # layer — no gathered prefix copy, pad queries (and fresh prefills'
        # empty prefix) skipped dead in-kernel. ``use_flash`` and
        # ``has_prefix`` are not read.
        from dynamo_tpu.engine.attention.megakernel import build_meta

        t_iq = jnp.arange(T, dtype=jnp.int32)
        mega_meta = build_meta(
            jnp.zeros((T,), jnp.int32),
            jnp.full((T,), prefix_rows, jnp.int32),
            jnp.zeros((T,), jnp.int32),
            t_iq + 1,
            (t_iq < valid_len).astype(jnp.int32),
        )

    scanned, experts = _split_expert_stacks(c, params["layers"])

    def layer_fn(h, xs):
        lp, l = xs  # l: scalar layer index
        lp = dequant_layer(lp, wdtype)  # int8 weight-only storage
        x = _norm(c, h, lp["attn_norm"], wdtype)
        q = project_heads(x, lp["wq"], c.num_heads, positions, c.rope_theta)
        k = project_heads(x, lp["wk"], c.num_kv_heads, positions, c.rope_theta)
        v = (x @ lp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)

        if walks_tiles:
            attn = _mega_attend_rows(
                c, q, k, v, k_flat, v_flat,
                (block_table + l * N)[None, :], mega_meta,
            ).astype(wdtype)
        else:
            # No kernel serves the pool: ragged chunk attention over
            # [gathered prefix ; chunk] — shared with the mixed step
            # (attention/ragged.py). The gather is bounded by the caller's
            # width-bucketed table; flash fresh chunks skip it.
            from dynamo_tpu.engine.attention.ragged import ragged_chunk_attention

            if use_flash and not has_prefix:
                k_ctx = v_ctx = None
            else:
                table_l = block_table + l * N
                k_ctx = _gather_kv(k_flat, table_l, wdtype).reshape(ctx, kvh, c.head_dim)
                v_ctx = _gather_kv(v_flat, table_l, wdtype).reshape(ctx, kvh, c.head_dim)
            attn = ragged_chunk_attention(
                q, k, v, k_ctx, v_ctx, valid_len, prefix_rows,
                num_kv_heads=kvh, use_flash=use_flash, has_prefix=has_prefix,
                interpret=interp,
            )
        h = h + attn.reshape(T, c.q_size) @ lp["wo"]

        x = _norm(c, h, lp["mlp_norm"], wdtype)
        if moe_stats:
            mlp_out, drops = _mlp(x, lp, c, valid=valid_q, stats=True, experts=experts, layer=l)
            h = h + mlp_out
            return h, (k, v, drops)
        h = h + _mlp(x, lp, c, valid=valid_q, experts=experts, layer=l)
        return h, (k, v)

    if moe_stats:
        h, (k_rows, v_rows, layer_drops) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32))
        )
        aux = {
            "moe_dropped": jnp.sum(layer_drops),
            "moe_assignments": jnp.sum(valid_q).astype(jnp.int32)
            * jnp.int32(max(c.num_experts_per_tok, 1) * L),
        }
    else:
        h, (k_rows, v_rows) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32))
        )

    # One all-layer scatter: [L, T] targets into the donated cache buffers.
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, T))
    k_new = _scatter_kv(k_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], k_rows)
    v_new = _scatter_kv(v_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], v_rows)

    logits = _logits(c, params, h if all_logits else h[jnp.maximum(valid_len - 1, 0)], wdtype)
    if moe_stats:
        return logits, k_new, v_new, aux
    return logits, k_new, v_new


def decode_multi(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    tokens: jax.Array,  # [B] current token per sequence
    positions: jax.Array,  # [B] write slot of the current token
    block_tables: jax.Array,  # [B, max_blocks] — must cover positions+num_steps
    active: jax.Array,  # [B] bool
    temps: jax.Array,  # [B] f32 (0 = greedy)
    top_ks: jax.Array,  # [B] i32 (0 = off)
    top_ps: jax.Array,  # [B] f32 (1 = off)
    rng_key: jax.Array,
    num_steps: int,
    moe_stats: bool = False,  # static: also return {"moe_dropped", "moe_assignments"}
    return_logits: bool = False,  # static: also return per-step logits [steps, B, V]
) -> Tuple[jax.Array, ...]:
    """``num_steps`` autoregressive decode steps + on-device sampling in ONE
    compiled dispatch. Returns (tokens_out [num_steps, B], k_cache, v_cache).

    The TPU-native answer to per-step dispatch overhead (the reference's
    engines expose the same lever as vLLM ``--num-scheduler-steps``): the
    sample→embed feedback loop stays on device, so the host syncs once per
    window instead of once per token. Stop conditions are checked on the
    host afterwards; tokens past a stop are trimmed by the scheduler.

    **Window-local KV**: the paged cache is READ-ONLY for the entire window.
    Each step's fresh K/V rows accumulate in a small carry
    (``[L, num_steps, B, KVH, HD]``) that attention folds in alongside the
    cached prefix, and ONE fused scatter writes the whole window afterwards.
    Scattering into the cache carry every step forced XLA into a full cache
    copy per iteration (scatter in-place elision does not fire for gather-
    indexed writes inside a while body — measured ~0.9 ms/step/tensor at 1B
    scale on v5e, dominating the step); the window carry is KV-row-sized, so
    the per-step write cost is proportional to tokens produced, not cache
    size."""
    if moe_stats and return_logits:
        raise NotImplementedError(
            "decode_multi: moe_stats and return_logits cannot be combined yet "
            "(the return tuples would be ambiguous to existing unpackers)"
        )
    from dynamo_tpu.engine.sampling import sample_batch

    c = config
    B = tokens.shape[0]
    L, KVH, HD = c.num_layers, c.num_kv_heads, c.head_dim
    bs = c.block_size

    # Cached-prefix mask is fixed for the whole window (the cache is not
    # written during it); window rows carry the in-flight tokens.
    rows0 = cache_rows(c, positions)  # cache row of each sequence's first step
    _, _, mask0 = decode_targets(rows0, block_tables, active, bs)

    # Hoist the cached-prefix gather out of the window loop: the prefix is
    # read-only for the whole window, so gathering it per step pays the
    # materialize-write + re-read (2× the true KV bytes) num_steps times
    # over. One gather up front amortizes that to 1/num_steps; each step
    # then streams the packed buffer (measured b32/ctx1024/w16 on v5e:
    # 9.7 → ~6.9 ms/step). Capped so wide-batch × long-context shapes don't
    # pin multi-GB buffers — past the cap the per-step gather path runs.
    wdtype = params["embed"].dtype
    ctx_w = block_tables.shape[1] * bs
    N = k_cache.shape[1]
    k_ctx_all = v_ctx_all = None
    hoist_bytes = 2 * L * B * ctx_w * KVH * HD * jnp.dtype(wdtype).itemsize
    if (
        num_steps > 1
        and not _use_paged_decode(c, k_cache)
        and not _use_megakernel(c, k_cache)
        and hoist_bytes <= _hoist_gather_budget()
    ):
        k_flat = layer_flat(k_cache)
        v_flat = layer_flat(v_cache)
        tables_all = block_tables[None] + (jnp.arange(L, dtype=jnp.int32) * N)[:, None, None]
        k_ctx_all = _gather_kv(k_flat, tables_all, wdtype).reshape(L, B, ctx_w, KVH, HD)
        v_ctx_all = _gather_kv(v_flat, tables_all, wdtype).reshape(L, B, ctx_w, KVH, HD)

    def body(i, state):
        toks, k_win, v_win, out, lg_out, key, drops = state
        poss = positions + i
        h, _ = _embed_rows(c, params, toks)  # [B, D]
        h, k_rows, v_rows, step_drops = _decode_layer_scan_window(
            params["layers"], c, k_cache, v_cache, h, poss, block_tables,
            mask0, k_win, v_win, i, active, moe_stats=moe_stats,
            k_ctx_all=k_ctx_all, v_ctx_all=v_ctx_all, prefix_rows=rows0, wdtype=wdtype,
        )
        k_win = k_win.at[:, i].set(k_rows)
        v_win = v_win.at[:, i].set(v_rows)
        logits = _logits(c, params, h, wdtype)
        key, sub = jax.random.split(key)
        nxt = sample_batch(logits, temps, top_ks, top_ps, sub).astype(jnp.int32)
        out = out.at[i].set(nxt)
        if return_logits:
            lg_out = lg_out.at[i].set(logits)
        return (nxt, k_win, v_win, out, lg_out, key, drops + step_drops)

    # Window rows are IN-FLIGHT real values (compute dtype) — int8 caches
    # only quantize at the final fused scatter. (cache.dtype would be int8
    # for QuantKv: scattering f32 rows into it is an unsafe cast — a JAX
    # FutureWarning today, an error in future releases — and would strip
    # the scales.)
    k_win0 = jnp.zeros((L, num_steps, B, KVH, HD), dtype=wdtype)
    v_win0 = jnp.zeros((L, num_steps, B, KVH, HD), dtype=wdtype)
    out0 = jnp.zeros((num_steps, B), dtype=jnp.int32)
    V = params["embed"].shape[0]
    lg0 = jnp.zeros((num_steps if return_logits else 1, B, V if return_logits else 1), jnp.float32)
    _, k_win, v_win, out, lg_steps, _, total_drops = lax.fori_loop(
        0, num_steps, body, (tokens, k_win0, v_win0, out0, lg0, rng_key, jnp.int32(0))
    )

    # One fused scatter for the whole window: row (l, j, b) → cache row
    # rows0_b + j (a window's rows are consecutive).
    steps_i = jnp.arange(num_steps, dtype=jnp.int32)
    live = active[None, :]
    if c.is_eva:
        # A sequence stops at its window boundary: the steps past it wait
        # for the roll (the scheduler takes none of their tokens), so their
        # rows, which would overwrite the completed window, sink to scratch.
        live = live & ((positions % c.window_size)[None, :] + steps_i[:, None] < c.window_size)
    slots = jnp.where(live, rows0[None, :] + steps_i[:, None], 0)  # [w, B]
    tgt_blocks = jnp.where(
        live, block_tables[jnp.arange(B)[None, :], slots // bs], 0
    )  # [w, B] — inactive rows sink to scratch block 0
    tgt_offs = slots % bs
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None, None], (L, num_steps, B))
    k_new = _scatter_kv(k_cache, layer_idx, tgt_blocks[None], tgt_offs[None], k_win)
    v_new = _scatter_kv(v_cache, layer_idx, tgt_blocks[None], tgt_offs[None], v_win)
    if moe_stats:
        aux = {
            "moe_dropped": total_drops,
            "moe_assignments": jnp.sum(active).astype(jnp.int32)
            * jnp.int32(max(c.num_experts_per_tok, 1) * L * num_steps),
        }
        return out, k_new, v_new, aux
    if return_logits:
        return out, lg_steps, k_new, v_new
    return out, k_new, v_new


def eva_roll_blocks(c: ModelConfig) -> int:
    """Blocks ``eva_roll`` is handed: those that hold a window's rows from
    the block of its first row on. One more than the window's own where the
    summaries of a window do not fill whole blocks, so that a window may
    begin inside a block."""
    bs, W = c.block_size, c.window_size
    aligned = c.summaries_per_window % bs == 0 and W % bs == 0
    return -(-W // bs) + (0 if aligned else 1)


def eva_roll(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    table: jax.Array,  # [eva_roll_blocks] block ids from the block that holds ``row0`` on
    row0: jax.Array,  # scalar i32: table row of the window's first key
) -> Tuple[jax.Array, jax.Array]:
    """Roll one completed window of one sequence: pool each chunk of its
    ``window_size`` exact (key, value) rows into one summary row and write
    the ``M = window_size // chunk_size`` summaries over the window's first
    ``M`` rows, in every layer. Returns (k_cache, v_cache); the caller then
    releases the blocks past the summaries.

    Per head ``a`` and chunk ``c`` (float32; ``mu``, ``phi`` learned per
    head and layer)::

        kbar[c, a] = sum_i softmax_i(mu_a . k[i, a]) k[i, a]
        vbar[c, a] = sum_i softmax_i(phi_a . k[i, a]) v[i, a]

    Keys are pooled as cached, i.e. after rope. The window's blocks are
    gathered through the layer-flat pool (no slice of a layer, no copy of
    the pool) and the summaries scattered in one all-layer write into the
    donated buffers: the bytes moved are the window read and ``M`` rows
    written per layer."""
    c = config
    bs, W, C, M = c.block_size, c.window_size, c.chunk_size, c.summaries_per_window
    L, KVH, HD = c.num_layers, c.num_kv_heads, c.head_dim
    N = k_cache.shape[1]
    nb = table.shape[0]
    k_flat, v_flat = layer_flat(k_cache), layer_flat(v_cache)
    off0 = row0 % bs

    def window_rows(flat, l):
        rows = _gather_kv(flat, table + l * N, flat.dtype).reshape(nb * bs, KVH * HD)
        if nb * bs != W:
            rows = lax.dynamic_slice_in_dim(rows, off0, W, axis=0)
        return split_heads(rows, KVH).astype(jnp.float32).reshape(M, C, KVH, HD)

    def pool(by, x, query):
        """Softmax-pooled ``x`` over each chunk, weighted by ``query . by``."""
        w = jax.nn.softmax(jnp.sum(by * query.astype(jnp.float32), axis=-1), axis=1)  # [M, C, KVH]
        return jnp.sum(w[..., None] * x, axis=1)  # [M, KVH, HD]

    def layer_fn(_, xs):
        mu, phi, l = xs
        k, v = window_rows(k_flat, l), window_rows(v_flat, l)
        return None, (pool(k, k, mu).astype(k_cache.dtype), pool(k, v, phi).astype(v_cache.dtype))

    layers = params["layers"]
    _, (k_bar, v_bar) = lax.scan(
        layer_fn, None, (layers["eva_mu"], layers["eva_phi"], jnp.arange(L, dtype=jnp.int32))
    )
    tgt = off0 + jnp.arange(M, dtype=jnp.int32)
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, M))
    blocks, offs = table[tgt // bs][None, :], (tgt % bs)[None, :]
    return (
        _scatter_kv(k_cache, layer_idx, blocks, offs, k_bar),
        _scatter_kv(v_cache, layer_idx, blocks, offs, v_bar),
    )


def _decode_layer_scan_window(
    layers: Dict[str, jax.Array],
    c: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD] — read-only throughout
    v_cache: jax.Array,
    h: jax.Array,  # [B, D]
    positions: jax.Array,  # [B] true position of the current token
    block_tables: jax.Array,  # [B, max_blocks]
    mask0: jax.Array,  # [B, ctx] cached-prefix mask (fixed at window start)
    k_win: jax.Array,  # [L, w, B, KVH, HD] window rows written so far
    v_win: jax.Array,
    step: jax.Array,  # scalar i — window rows j < i are live
    active: jax.Array,  # [B] bool
    moe_stats: bool = False,
    k_ctx_all: Optional[jax.Array] = None,  # [L, B, ctx, KVH, HD] pre-gathered
    v_ctx_all: Optional[jax.Array] = None,
    prefix_rows: Optional[jax.Array] = None,  # [B] cache rows below the window's first (default: its position)
    wdtype=None,  # compute dtype of the matmul inputs (default: h's)
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Decode layer scan attending [cached prefix ; window rows ; current].
    Same math as ``decode_layer_scan`` — the window rows are exactly the
    tokens a per-step cache write would have placed at positions
    pos0..pos0+i-1, read from the carry instead of the cache.

    When ``k_ctx_all``/``v_ctx_all`` are given, the cached prefix was
    gathered ONCE for the whole window (see decode_multi) and the scan
    reads per-layer slices instead of re-gathering — the gather's
    materialize-write plus re-read otherwise recurs every window step on a
    prefix that is read-only for the window's duration (measured at
    b32/ctx1024 on v5e: 4.6 ms of a 9.7 ms step in the prefix piece vs a
    1.6 ms true-bytes floor)."""
    B = h.shape[0]
    bs = c.block_size
    ctx = block_tables.shape[1] * bs
    w = k_win.shape[1]
    wdtype = h.dtype if wdtype is None else wdtype
    kvh, G, hd = c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim
    scale = hd**-0.5
    # Layer-flat cache views (see prefill): the scan gathers with
    # layer-offset tables instead of slicing the cache per layer.
    L = k_cache.shape[0]
    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    v_flat = layer_flat(v_cache)
    # Small-piece mask: window rows j < step, then the current token (always).
    small_mask = jnp.concatenate(
        [
            jnp.broadcast_to((jnp.arange(w, dtype=jnp.int32) < step)[None, :], (B, w)),
            jnp.ones((B, 1), dtype=bool),
        ],
        axis=1,
    )  # [B, w+1]

    hoisted = k_ctx_all is not None
    use_paged = not hoisted and _use_paged_decode(c, k_cache)
    use_mega = not hoisted and _use_megakernel(c, k_cache)
    # Prefix length is fixed for the whole window (mask0 semantics): the
    # window rows live in the carry, not the cache.
    if prefix_rows is None:
        prefix_rows = positions - step
    win_prefix_lens = jnp.minimum(prefix_rows, ctx).astype(jnp.int32)
    if use_mega:
        # Megakernel row metadata: each decode query's fresh keys are its
        # row's slice of [current ; window rows] — a contiguous [start,
        # end) column window, end advancing with the in-window step (the
        # not-yet-written carry rows stay masked for free).
        from dynamo_tpu.engine.attention.megakernel import build_meta

        rows_i = jnp.arange(B, dtype=jnp.int32)
        mega_meta = build_meta(
            rows_i, win_prefix_lens, rows_i * (w + 1), rows_i * (w + 1) + 1 + step, active,
        )
        mega_work = _mega_rows_work(c, k_cache, win_prefix_lens, active, block_tables.shape[1])

    scanned, experts = _split_expert_stacks(c, layers)

    def layer_fn(h, xs):
        if hoisted:
            lp, l, kwl, vwl, k_ctx, v_ctx = xs
        else:
            lp, l, kwl, vwl = xs  # kwl/vwl: [w, B, KVH, HD] this layer's window rows
        lp = dequant_layer(lp, wdtype)  # int8 weight-only storage
        x = _norm(c, h, lp["attn_norm"], wdtype)
        q = project_heads(x, lp["wq"], c.num_heads, positions, c.rope_theta)
        k = project_heads(x, lp["wk"], c.num_kv_heads, positions, c.rope_theta)
        v = (x @ lp["wv"]).reshape(B, c.num_kv_heads, c.head_dim)
        qg = q.reshape(B, kvh, G, hd)

        if use_mega:
            # ONE launch: paged prefix + [current ; live window rows] —
            # the carry rows ride as the kernel's fresh-key piece.
            k_extra = jnp.concatenate(
                [k[:, None], jnp.swapaxes(kwl, 0, 1)], axis=1
            ).reshape(B * (w + 1), kvh, hd)
            v_extra = jnp.concatenate(
                [v[:, None], jnp.swapaxes(vwl, 0, 1)], axis=1
            ).reshape(B * (w + 1), kvh, hd)
            attn = _mega_attend_rows(
                c, q, k_extra, v_extra, k_flat, v_flat,
                block_tables + l * N, mega_meta, mega_work,
            ).astype(wdtype)
            h = h + attn.reshape(B, c.q_size) @ lp["wo"]
            x = _norm(c, h, lp["mlp_norm"], wdtype)
            if moe_stats:
                mlp_out, drops = _mlp(x, lp, c, valid=active, stats=True, experts=experts, layer=l)
                return h + mlp_out, (k, v, drops)
            h = h + _mlp(x, lp, c, valid=active, experts=experts, layer=l)
            return h, (k, v)
        if use_paged:
            m1, l1, acc1 = _paged_prefix_partials(
                c, q, k_flat, v_flat, block_tables + l * N, win_prefix_lens
            )
        else:
            if not hoisted:
                tables_l = block_tables + l * N
                # Piece 1: cached prefix via the width-bucketed gather (two-
                # piece online-softmax — no concat re-materialization).
                k_ctx = _gather_kv(k_flat, tables_l, wdtype).reshape(B, ctx, kvh, hd)
                v_ctx = _gather_kv(v_flat, tables_l, wdtype).reshape(B, ctx, kvh, hd)
            m1, l1, acc1 = _attend_piece(qg, k_ctx, v_ctx, mask0, scale)
        # Piece 2: in-register rows [window ; current] — never round-trip HBM.
        k_small = jnp.concatenate([jnp.swapaxes(kwl, 0, 1), k[:, None]], axis=1)  # [B, w+1, ...]
        v_small = jnp.concatenate([jnp.swapaxes(vwl, 0, 1), v[:, None]], axis=1)
        m2, l2, acc2 = _attend_piece(qg, k_small, v_small, small_mask, scale)
        attn = _merge_pieces(m1, l1, acc1, m2, l2, acc2).astype(wdtype)

        h = h + attn.reshape(B, c.q_size) @ lp["wo"]
        x = _norm(c, h, lp["mlp_norm"], wdtype)
        if moe_stats:
            mlp_out, drops = _mlp(x, lp, c, valid=active, stats=True, experts=experts, layer=l)
            return h + mlp_out, (k, v, drops)
        h = h + _mlp(x, lp, c, valid=active, experts=experts, layer=l)
        return h, (k, v)

    xs = (scanned, jnp.arange(L, dtype=jnp.int32), k_win, v_win)
    if hoisted:
        xs = xs + (k_ctx_all, v_ctx_all)
    if moe_stats:
        h, (k_rows, v_rows, layer_drops) = lax.scan(layer_fn, h, xs)
        return h, k_rows, v_rows, jnp.sum(layer_drops)
    h, (k_rows, v_rows) = lax.scan(layer_fn, h, xs)
    return h, k_rows, v_rows, jnp.int32(0)


def chunk_decode(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    tokens: jax.Array,  # [B, S] per-row token chunks (padded)
    positions0: jax.Array,  # [B] position of tokens[:, 0]
    valid: jax.Array,  # [B] valid tokens per row (0 = inactive row)
    block_tables: jax.Array,  # [B, W]
    all_logits: bool = False,  # static: return logits [B, S, V] instead of argmax
    moe_stats: bool = False,  # static: also return {"moe_dropped", "moe_assignments"}
    last_logits: bool = False,  # static: return only each row's last-valid logits [B, V]
) -> Tuple[jax.Array, ...]:
    """Batched multi-token decode: each row consumes up to S tokens in ONE
    pass and yields the greedy next-token prediction after every consumed
    position → (argmax tokens [B, S] i32, k_cache, v_cache) — or the full
    per-position logits with ``all_logits=True``, or only the last valid
    position's logits per row with ``last_logits=True`` (the batched-
    admission prefill path: one dispatch prefills a WAVE of short prompts
    and feeds the sampler directly).

    This is the engine primitive behind batched speculative decoding
    (spec_decode.py; ref surfaces SpecDecodeStats, _core.pyi:354-427): the
    target model verifies γ+1-token chunks for the whole batch in one
    MXU-friendly pass, and the draft model uses the same op to catch up on
    accepted tokens. KV rows for all S slots are written (stale-ok: rows
    past a row's accepted prefix are position-masked until the real token
    at that position overwrites them — write-before-attend, monotone
    positions)."""
    c = config
    _refuse_eva(c, "chunk_decode (wave admission, speculative verification)")
    bs = c.block_size
    B, S = tokens.shape
    L, KVH, HD = c.num_layers, c.num_kv_heads, c.head_dim
    kvh, G, hd = KVH, c.num_heads // KVH, HD
    ctx = block_tables.shape[1] * bs
    scale = hd**-0.5
    active = valid > 0

    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    v_flat = layer_flat(v_cache)

    h = params["embed"].at[tokens].get(mode="clip")  # [B, S, D]
    wdtype = h.dtype
    positions = positions0[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B, S]

    # Prefix mask: cached keys strictly before the chunk. Chunk mask: causal
    # within the chunk, limited to each row's valid tokens.
    key_pos = jnp.arange(ctx, dtype=jnp.int32)
    prefix_mask = key_pos[None, :] < positions0[:, None]  # [B, ctx]
    s_i = jnp.arange(S, dtype=jnp.int32)
    chunk_mask = (s_i[None, None, :] <= s_i[None, :, None]) & (
        s_i[None, None, :] < valid[:, None, None]
    )  # [B, S_q, S_k]

    def piece(qg, kp, vp, maskp):
        """qg [B,S,KVH,G,hd]; kp/vp [B,S_k,KVH,hd]; maskp [B,(S_q,)S_k] →
        online-softmax partials (m, l, acc) with S_q query positions."""
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kp).astype(jnp.float32) * scale
        if maskp.ndim == 2:
            m_b = maskp[:, None, None, None, :]
        else:
            m_b = maskp[:, None, None, :, :]
        s = jnp.where(m_b, s, -1e30)
        m = jnp.max(s, axis=-1)  # [B,KVH,G,S_q]
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vp.dtype), vp).astype(jnp.float32)
        return m, l, acc

    scanned, experts = _split_expert_stacks(c, params["layers"])

    def layer_fn(h, xs):
        lp, l = xs
        lp = dequant_layer(lp, wdtype)  # int8 weight-only storage
        x = _norm(c, h, lp["attn_norm"], wdtype)
        q = project_heads(x, lp["wq"], c.num_heads, positions, c.rope_theta)
        k = project_heads(x, lp["wk"], kvh, positions, c.rope_theta)
        v = (x @ lp["wv"]).reshape(B, S, kvh, hd)
        qg = q.reshape(B, S, kvh, G, hd)

        tables_l = block_tables + l * N
        k_ctx = _gather_kv(k_flat, tables_l, wdtype).reshape(B, ctx, kvh, hd)
        v_ctx = _gather_kv(v_flat, tables_l, wdtype).reshape(B, ctx, kvh, hd)
        m1, l1, acc1 = piece(qg, k_ctx, v_ctx, prefix_mask)
        m2, l2, acc2 = piece(qg, k, v, chunk_mask)
        m_t = jnp.maximum(m1, m2)
        a1 = jnp.exp(m1 - m_t)
        a2 = jnp.exp(m2 - m_t)
        l_t = l1 * a1 + l2 * a2
        acc = acc1 * a1[..., None] + acc2 * a2[..., None]
        attn = (acc / jnp.maximum(l_t, 1e-30)[..., None]).astype(wdtype)  # [B,KVH,G,S,hd]
        attn = jnp.transpose(attn, (0, 3, 1, 2, 4)).reshape(B, S, c.q_size)

        h = h + attn @ lp["wo"]
        x = _norm(c, h, lp["mlp_norm"], wdtype)
        valid_flat = (s_i[None, :] < valid[:, None]).reshape(B * S)
        if moe_stats:
            mlp_out, drops = _mlp(x.reshape(B * S, -1), lp, c, valid=valid_flat, stats=True, experts=experts, layer=l)
            h = h + mlp_out.reshape(B, S, -1)
            return h, (k, v, drops)
        mlp_out = _mlp(x.reshape(B * S, -1), lp, c, valid=valid_flat, experts=experts, layer=l).reshape(B, S, -1)
        h = h + mlp_out
        return h, (k, v)

    if moe_stats:
        h, (k_rows, v_rows, layer_drops) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32))
        )
        chunk_aux = {
            "moe_dropped": jnp.sum(layer_drops),
            "moe_assignments": jnp.sum(valid).astype(jnp.int32)
            * jnp.int32(max(c.num_experts_per_tok, 1) * L),
        }
    else:
        h, (k_rows, v_rows) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32))
        )

    # Fused scatter of all chunk rows: slot (b, s) → positions0[b]+s when
    # s < valid[b], else the scratch sink (block 0 of each layer).
    live = s_i[None, :] < valid[:, None]  # [B, S]
    slots = jnp.where(live, positions, 0)
    tgt_blocks = jnp.where(
        live, jnp.take_along_axis(block_tables, slots // bs, axis=1), 0
    )  # [B, S]
    tgt_offs = slots % bs
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None, None], (L, B, S))
    # k_rows: [L, B, S, KVH, HD]
    k_new = _scatter_kv(k_cache, layer_idx, tgt_blocks[None], tgt_offs[None], k_rows)
    v_new = _scatter_kv(v_cache, layer_idx, tgt_blocks[None], tgt_offs[None], v_rows)

    h = rms_norm(h, params["final_norm"], c.rms_norm_eps)
    head = params.get("lm_head")
    if last_logits:
        # Batched-admission prefill: only each row's LAST valid position
        # feeds sampling, so the lm_head runs on [B, D] picked rows, not
        # [B, S, D] — and the returned logits are sampler-sized ([B, V],
        # not a [B, S, V] buffer that would be GBs at real vocab sizes).
        last = jnp.maximum(valid - 1, 0)  # [B]
        h_last = jnp.take_along_axis(h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        lg = (h_last @ (head if head is not None else params["embed"].T)).astype(jnp.float32)
        if moe_stats:
            return lg, k_new, v_new, chunk_aux
        return lg, k_new, v_new
    logits = h @ (head if head is not None else params["embed"].T)  # [B, S, V]
    if all_logits:
        # Sampled speculative verification needs the full target
        # distributions per position (spec_decode.spec_verify).
        if moe_stats:
            return logits.astype(jnp.float32), k_new, v_new, chunk_aux
        return logits.astype(jnp.float32), k_new, v_new
    next_tokens = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    if moe_stats:
        return next_tokens, k_new, v_new, chunk_aux
    return next_tokens, k_new, v_new


def mixed_step(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    p_tokens: jax.Array,  # [S] prefill-chunk token ids (bucket-padded)
    p_valid: jax.Array,  # scalar i32: actual chunk tokens (the row's ``len``)
    p_cache_len: jax.Array,  # scalar i32: tokens already materialized (``start``)
    p_table: jax.Array,  # [Wp] the chunk sequence's block table (width-bucketed)
    d_tokens: jax.Array,  # [B] current token per decode row
    d_positions: jax.Array,  # [B] write slot of each decode token
    d_tables: jax.Array,  # [B, Wd] decode block tables
    d_active: jax.Array,  # [B] bool — padded decode lanes are False
    use_flash: bool = False,  # static: Pallas flash kernel for the chunk piece
    has_prefix: bool = True,  # static on flash: False ⇒ p_cache_len == 0
    moe_stats: bool = False,  # static: also return {"moe_dropped", "moe_assignments"}
) -> Tuple[jax.Array, ...]:
    """One MIXED engine step: a ragged prefill chunk + the full decode batch
    in ONE compiled dispatch. Returns ``(logits [1+B, V] f32, k_cache,
    v_cache)`` — row 0 is the chunk's last-valid position (the prompt's
    next-token logits once the chunk completes it), rows 1.. are the decode
    rows. Sampling happens only at each sequence's last row: decode entries
    are their own last row; the chunk contributes exactly one.

    This dissolves the prefill/decode phase boundary: the flat token axis
    is ``[chunk row (start=p_cache_len, len=p_valid) ; B length-1 decode
    rows]``. Projections, MLP, and the final fused KV scatter run over the
    whole ragged batch (decode matmuls alone leave the MXU idle — the chunk
    tokens ride the same dispatch instead of stalling behind it), while
    attention splits into the two shapes it actually has: the chunk (the
    megakernel's tile walk wherever a kernel serves the pool,
    ``chunk_walks_tiles``; else attention/ragged.py — width-bucketed prefix
    gather + causal chunk, flash kernel opt-in) and the decode rows (the
    kernel their impl names, or the two-piece online-softmax of gathered
    prefix + current token in-register), identical math to ``prefill`` and
    ``decode`` respectively."""
    c = config
    bs = c.block_size
    S = p_tokens.shape[0]
    B = d_tokens.shape[0]
    L, KVH, HD = c.num_layers, c.num_kv_heads, c.head_dim
    kvh, G, hd = KVH, c.num_heads // KVH, HD
    scale = hd**-0.5
    interp = not _on_tpu()

    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    v_flat = layer_flat(v_cache)

    p_positions = p_cache_len + jnp.arange(S, dtype=jnp.int32)
    p_valid_q = jnp.arange(S, dtype=jnp.int32) < p_valid
    positions_all = jnp.concatenate([p_positions, d_positions])
    valid_all = jnp.concatenate([p_valid_q, d_active])
    h, wdtype = _embed_rows(c, params, jnp.concatenate([p_tokens, d_tokens]))  # [S+B, D]
    # Positions turn the rope; the cache is addressed by row (cache_rows).
    p_prefix_rows = cache_rows(c, p_cache_len)
    d_rows = cache_rows(c, d_positions)

    ctx_p = p_table.shape[0] * bs
    ctx_d = d_tables.shape[1] * bs
    d_tgt_blocks, d_tgt_offs, d_mask = decode_targets(d_rows, d_tables, d_active, bs)
    use_paged = _use_paged_decode(c, k_cache)
    use_mega = _use_megakernel(c, k_cache)
    walks_tiles = chunk_walks_tiles(c, k_cache)
    d_prefix_lens = jnp.minimum(d_rows, ctx_d).astype(jnp.int32)
    # The mixed step's attention is the two shapes it has, each a launch per
    # layer where a kernel serves the pool. The chunk, a wide row walked by
    # tiles of its queries over its own table and its own fresh keys
    # (chunk_walks_tiles): its padded slots hold the scratch page and are
    # skipped (pl.when) along with its bucket's dead queries and a fresh
    # chunk's empty prefix, so ``use_flash`` and ``has_prefix`` are not read.
    # And the B length-1 decode rows, through the kernel their impl names:
    # the megakernel's launch over the list of their live pages (build_work:
    # an inactive lane or a padded table slot is no step of it), or the paged
    # kernel's prefix partials merged with the current token in-register.
    if walks_tiles:
        from dynamo_tpu.engine.attention.megakernel import build_meta

        s_iq = jnp.arange(S, dtype=jnp.int32)
        d_iq = jnp.arange(B, dtype=jnp.int32)
        p_meta = build_meta(
            jnp.zeros((S,), jnp.int32), jnp.full((S,), p_prefix_rows, jnp.int32),
            jnp.zeros((S,), jnp.int32), s_iq + 1, s_iq < p_valid,
        )
    if use_mega:
        d_meta = build_meta(d_iq, d_prefix_lens, d_iq, d_iq + 1, d_active)
        d_work = _mega_rows_work(c, k_cache, d_prefix_lens, d_active, d_tables.shape[1])

    from dynamo_tpu.engine.attention.ragged import ragged_chunk_attention

    scanned, experts = _split_expert_stacks(c, params["layers"])

    def layer_fn(h, xs):
        lp, l = xs
        lp = dequant_layer(lp, wdtype)  # int8 weight-only storage
        x = _norm(c, h, lp["attn_norm"], wdtype)
        q = project_heads(x, lp["wq"], c.num_heads, positions_all, c.rope_theta)
        k = project_heads(x, lp["wk"], kvh, positions_all, c.rope_theta)
        v = (x @ lp["wv"]).reshape(S + B, kvh, hd)

        # Chunk piece [S, H, hd]; each piece's fresh keys are its own rows of
        # the projection.
        if walks_tiles:
            attn_p = _mega_attend_rows(
                c, q[:S], k[:S], v[:S], k_flat, v_flat, (p_table + l * N)[None, :], p_meta,
            )
        else:
            # [gathered prefix ; chunk] — prefill's exact math where no
            # kernel serves the pool.
            if use_flash and not has_prefix:
                kp_ctx = vp_ctx = None
            else:
                table_pl = p_table + l * N
                kp_ctx = _gather_kv(k_flat, table_pl, wdtype).reshape(ctx_p, kvh, hd)
                vp_ctx = _gather_kv(v_flat, table_pl, wdtype).reshape(ctx_p, kvh, hd)
            attn_p = ragged_chunk_attention(
                q[:S], k[:S], v[:S], kp_ctx, vp_ctx, p_valid, p_prefix_rows,
                num_kv_heads=kvh, use_flash=use_flash, has_prefix=has_prefix,
                interpret=interp,
            )

        # Decode rows [B, H, hd].
        if use_mega:
            attn_d = _mega_attend_rows(c, q[S:], k[S:], v[S:], k_flat, v_flat, d_tables + l * N, d_meta, d_work)
        else:
            # Cached prefix + current token in-register — the
            # decode_layer_scan two-piece merge.
            qg_d = q[S:].reshape(B, kvh, G, hd)
            if use_paged:
                m1, l1, acc1 = _paged_prefix_partials(
                    c, q[S:], k_flat, v_flat, d_tables + l * N, d_prefix_lens
                )
            else:
                tables_dl = d_tables + l * N
                kd_ctx = _gather_kv(k_flat, tables_dl, wdtype).reshape(B, ctx_d, kvh, hd)
                vd_ctx = _gather_kv(v_flat, tables_dl, wdtype).reshape(B, ctx_d, kvh, hd)
                m1, l1, acc1 = _attend_piece(qg_d, kd_ctx, vd_ctx, d_mask, scale)
            m2, l2, acc2 = _attend_piece(
                qg_d, k[S:, None], v[S:, None], jnp.ones((B, 1), dtype=bool), scale
            )
            attn_d = _merge_pieces(m1, l1, acc1, m2, l2, acc2).astype(wdtype).reshape(B, c.num_heads, hd)

        attn = jnp.concatenate([attn_p, attn_d]).astype(wdtype).reshape(S + B, c.q_size)
        h = h + attn @ lp["wo"]
        x = _norm(c, h, lp["mlp_norm"], wdtype)
        if moe_stats:
            mlp_out, drops = _mlp(x, lp, c, valid=valid_all, stats=True, experts=experts, layer=l)
            return h + mlp_out, (k, v, drops)
        h = h + _mlp(x, lp, c, valid=valid_all, experts=experts, layer=l)
        return h, (k, v)

    if moe_stats:
        h, (k_rows, v_rows, layer_drops) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32))
        )
        aux = {
            "moe_dropped": jnp.sum(layer_drops),
            "moe_assignments": jnp.sum(valid_all).astype(jnp.int32)
            * jnp.int32(max(c.num_experts_per_tok, 1) * L),
        }
    else:
        h, (k_rows, v_rows) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(L, dtype=jnp.int32))
        )

    # ONE fused ragged scatter for chunk rows + decode rows together.
    p_tgt_blocks, p_tgt_offs = ragged_scatter_targets(p_table, cache_rows(c, p_positions), p_valid_q, bs)
    tgt_blocks = jnp.concatenate([p_tgt_blocks, d_tgt_blocks])
    tgt_offs = jnp.concatenate([p_tgt_offs, d_tgt_offs])
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, S + B))
    k_new = _scatter_kv(k_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], k_rows)
    v_new = _scatter_kv(v_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], v_rows)

    # lm_head only at each sequence's LAST row: the chunk's last valid
    # position + every decode row — [1+B, D] picked rows, never [S+B, V].
    last_p = jnp.maximum(p_valid - 1, 0)
    logits = _logits(c, params, jnp.concatenate([h[last_p][None], h[S:]], axis=0), wdtype)
    if moe_stats:
        return logits, k_new, v_new, aux
    return logits, k_new, v_new


def embed(
    params: Params,
    config: ModelConfig,
    tokens: jax.Array,  # [T] bucket-padded token ids
    valid_len: jax.Array,  # scalar
) -> jax.Array:
    """Sequence embedding: full causal forward (no KV cache), masked mean
    pool over the final hidden states → [hidden_size] f32, L2-normalized.
    (Serving path for /v1/embeddings — ref: http/service/openai.rs:369.)"""
    c = config
    _refuse_eva(c, "embed (sequence embeddings)")
    T = tokens.shape[0]
    h = params["embed"].at[tokens].get(mode="clip")  # [T, D]
    wdtype = h.dtype
    positions = jnp.arange(T, dtype=jnp.int32)
    valid = positions < valid_len
    mask = (positions[None, :] <= positions[:, None]) & valid[None, :]

    scanned, experts = _split_expert_stacks(c, params["layers"])

    def layer_fn(h, xs):
        lp, l = xs
        lp = dequant_layer(lp, wdtype)  # int8 weight-only storage
        x = _norm(c, h, lp["attn_norm"], wdtype)
        q = project_heads(x, lp["wq"], c.num_heads, positions, c.rope_theta)
        k = project_heads(x, lp["wk"], c.num_kv_heads, positions, c.rope_theta)
        v = (x @ lp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)
        attn = _attend(q, k, v, mask, c)
        h = h + attn.reshape(T, c.q_size) @ lp["wo"]
        x = _norm(c, h, lp["mlp_norm"], wdtype)
        h = h + _mlp(x, lp, c, valid=valid, experts=experts, layer=l)
        return h, None

    h, _ = lax.scan(layer_fn, h, (scanned, jnp.arange(c.num_layers, dtype=jnp.int32)))
    h = rms_norm(h, params["final_norm"], c.rms_norm_eps).astype(jnp.float32)
    weights = valid.astype(jnp.float32)[:, None]
    pooled = jnp.sum(h * weights, axis=0) / jnp.maximum(jnp.sum(weights), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def decode_targets(
    positions: jax.Array,  # [B] cache row of each current token (kv_cache.cache_rows)
    block_tables: jax.Array,  # [B, max_blocks]
    active: jax.Array,  # [B] bool
    block_size: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Paged-KV scatter targets + cached-prefix mask for one decode step.

    Inactive rows sink to scratch block 0 (never allocated). Returns
    (tgt_blocks [B], tgt_offs [B], mask [B, ctx]). The mask covers the
    CACHED prefix only (key_pos < positions) — the current token's K/V is
    folded into attention in-register, not read back from the cache. Shared
    by ``decode`` and the pipelined path so the addressing convention lives
    in one place."""
    slots = jnp.where(active, positions, 0)
    tgt_blocks = jnp.where(
        active, jnp.take_along_axis(block_tables, (slots // block_size)[:, None], axis=1)[:, 0], 0
    )
    tgt_offs = slots % block_size
    ctx = block_tables.shape[1] * block_size
    key_pos = jnp.arange(ctx, dtype=jnp.int32)
    mask = key_pos[None, :] < positions[:, None]  # [B, ctx] — cached prefix
    return tgt_blocks, tgt_offs, mask


def decode_layer_scan(
    layers: Dict[str, jax.Array],
    c: ModelConfig,
    k_cache: jax.Array,  # [L', N, BS, KVH*HD] — full stack or a pipeline stage's slice
    v_cache: jax.Array,
    h: jax.Array,  # [B, D] embedded inputs (or activations from the previous pp stage)
    positions: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, max_blocks]
    mask: jax.Array,  # [B, ctx] bool — cached prefix only (decode_targets)
    active: Optional[jax.Array] = None,  # [B] bool — live lanes (MoE dispatch mask)
    moe_stats: bool = False,  # also return summed capacity drops
    wdtype=None,  # compute dtype of the matmul inputs (default: h's)
):
    """Scan the decode layer body over a stacked layer group. Factored out of
    ``decode`` so pipeline parallelism (pipeline_parallel.py) can run the
    same body on each stage's local L/pp slice of layers + KV cache.

    The cache is READ-ONLY here: per-layer slices ride the scan xs and each
    layer's new K/V row is attended in-register (appended to the gathered
    context / folded into the kernel's online softmax) and returned stacked
    ``[L', B, KVH, HD]`` for the caller's single fused scatter. Writing the
    cache inside the scan carry forced XLA into a full cache copy per layer
    (seen before PR 1; its cost is not measured on today's code);
    read-only xs slicing leaves the buffers untouched."""
    B = h.shape[0]
    bs = c.block_size
    ctx = block_tables.shape[1] * bs
    # Layer-flat cache views (see prefill): no per-layer slice copies in the
    # scan — gathers index [L'*N, ...] with layer-offset tables instead.
    Lp = k_cache.shape[0]
    N = k_cache.shape[1]
    k_flat = layer_flat(k_cache)
    v_flat = layer_flat(v_cache)

    kvh, G, hd = c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim
    scale = hd**-0.5
    use_paged = _use_paged_decode(c, k_cache)
    use_mega = _use_megakernel(c, k_cache)
    wdtype = h.dtype if wdtype is None else wdtype
    prefix_lens = jnp.minimum(cache_rows(c, positions), ctx).astype(jnp.int32)
    if use_mega:
        from dynamo_tpu.engine.attention.megakernel import build_meta

        rows_i = jnp.arange(B, dtype=jnp.int32)
        live = jnp.ones((B,), bool) if active is None else active
        mega_meta = build_meta(rows_i, prefix_lens, rows_i, rows_i + 1, live)
        mega_work = _mega_rows_work(c, k_cache, prefix_lens, live, block_tables.shape[1])

    scanned, experts = _split_expert_stacks(c, layers)

    def layer_fn(h, xs):
        lp, l = xs  # l: scalar layer index within this stack
        lp = dequant_layer(lp, wdtype)  # int8 weight-only storage
        x = _norm(c, h, lp["attn_norm"], wdtype)
        q = project_heads(x, lp["wq"], c.num_heads, positions, c.rope_theta)  # [B, H, hd]
        k = project_heads(x, lp["wk"], c.num_kv_heads, positions, c.rope_theta)  # [B, KVH, hd]
        v = (x @ lp["wv"]).reshape(B, c.num_kv_heads, c.head_dim)
        qg = q.reshape(B, kvh, G, hd)

        tables_l = block_tables + l * N
        if use_mega:
            # Ragged megakernel: prefix pages + the current token merge
            # inside ONE launch's online softmax — no gathered copy, no
            # external piece merge (attention/megakernel.py).
            attn = _mega_attend_rows(
                c, q, k, v, k_flat, v_flat, tables_l, mega_meta, mega_work
            ).astype(wdtype)
        else:
            # Two online-softmax pieces: cached prefix + current token
            # in-register. Prefix: Pallas paged flash kernel (pages stream
            # HBM→VMEM once) or the width-bucketed XLA gather fallback.
            if use_paged:
                m1, l1, acc1 = _paged_prefix_partials(c, q, k_flat, v_flat, tables_l, prefix_lens)
            else:
                k_ctx = _gather_kv(k_flat, tables_l, wdtype).reshape(B, ctx, kvh, hd)
                v_ctx = _gather_kv(v_flat, tables_l, wdtype).reshape(B, ctx, kvh, hd)
                m1, l1, acc1 = _attend_piece(qg, k_ctx, v_ctx, mask, scale)
            m2, l2, acc2 = _attend_piece(
                qg, k[:, None], v[:, None], jnp.ones((B, 1), dtype=bool), scale
            )
            attn = _merge_pieces(m1, l1, acc1, m2, l2, acc2).astype(wdtype)
        h = h + attn.reshape(B, c.q_size) @ lp["wo"]

        x = _norm(c, h, lp["mlp_norm"], wdtype)
        if moe_stats:
            mlp_out, drops = _mlp(x, lp, c, valid=active, stats=True, experts=experts, layer=l)
            return h + mlp_out, (k, v, drops)
        h = h + _mlp(x, lp, c, valid=active, experts=experts, layer=l)
        return h, (k, v)

    if moe_stats:
        h, (k_rows, v_rows, layer_drops) = lax.scan(
            layer_fn, h, (scanned, jnp.arange(Lp, dtype=jnp.int32))
        )
        return h, k_rows, v_rows, jnp.sum(layer_drops)
    h, (k_rows, v_rows) = lax.scan(
        layer_fn, h, (scanned, jnp.arange(Lp, dtype=jnp.int32))
    )
    return h, k_rows, v_rows


def scatter_kv_rows(
    k_cache: jax.Array,  # [L', N, BS, KVH*HD]
    v_cache: jax.Array,
    k_rows: jax.Array,  # [L', B, KVH, HD] from decode_layer_scan
    v_rows: jax.Array,
    tgt_blocks: jax.Array,  # [B]
    tgt_offs: jax.Array,  # [B]
) -> Tuple[jax.Array, jax.Array]:
    """Single fused all-layer KV write (one scatter per cache tensor)."""
    L, B = k_rows.shape[0], k_rows.shape[1]
    layer_idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[:, None], (L, B))
    k_new = _scatter_kv(k_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], k_rows)
    v_new = _scatter_kv(v_cache, layer_idx, tgt_blocks[None, :], tgt_offs[None, :], v_rows)
    return k_new, v_new


def decode(
    params: Params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD]
    v_cache: jax.Array,
    tokens: jax.Array,  # [B] current token per sequence
    positions: jax.Array,  # [B] position of each token (its write slot)
    block_tables: jax.Array,  # [B, max_blocks]
    active: jax.Array,  # [B] bool — padded batch slots are False
    moe_stats: bool = False,  # static: also return {"moe_dropped", "moe_assignments"}
) -> Tuple[jax.Array, ...]:
    """One decode step for a batch. Returns (logits [B, V], k_cache, v_cache)
    (+ capacity-MoE drop aux with ``moe_stats``)."""
    c = config
    bs = c.block_size

    h, wdtype = _embed_rows(c, params, tokens)  # [B, D]

    tgt_blocks, tgt_offs, mask = decode_targets(cache_rows(c, positions), block_tables, active, bs)

    # Decode attention: the ragged megakernel (one launch per layer, TPU
    # auto) or the width-bucketed XLA gather with a two-piece online-
    # softmax merge — see ModelConfig.attention_impl for the full record.
    if moe_stats:
        h, k_rows, v_rows, drops = decode_layer_scan(
            params["layers"], c, k_cache, v_cache, h, positions,
            block_tables, mask, active=active, moe_stats=True, wdtype=wdtype,
        )
    else:
        h, k_rows, v_rows = decode_layer_scan(
            params["layers"], c, k_cache, v_cache, h, positions,
            block_tables, mask, active=active, wdtype=wdtype,
        )
    k_new, v_new = scatter_kv_rows(k_cache, v_cache, k_rows, v_rows, tgt_blocks, tgt_offs)

    logits = _logits(c, params, h, wdtype)
    if moe_stats:
        aux = {
            "moe_dropped": drops,
            "moe_assignments": jnp.sum(active).astype(jnp.int32)
            * jnp.int32(max(c.num_experts_per_tok, 1) * c.num_layers),
        }
        return logits, k_new, v_new, aux
    return logits, k_new, v_new
