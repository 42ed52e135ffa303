"""Pipeline parallelism: microbatched ppermute pipeline over pp-sharded layers.

The reference only *configures* pipeline parallel in its engines (trtllm
``pipeline_parallel_size``, SURVEY.md §2e) — the actual pipelining lives in
TRT-LLM/vLLM CUDA runtimes. Here it is native and TPU-idiomatic:

- The model keeps its stacked-layer layout (``[L, ...]`` leaves, scanned by
  ``lax.scan``). The stack shards over the ``pp`` mesh axis — each stage
  holds ``L/pp`` contiguous layers and the matching slice of the paged KV
  cache (``kv_cache_spec(pp=True)``), so HBM per chip drops by pp×.
- A partial-manual ``jax.shard_map(axis_names={'pp'})`` makes only ``pp``
  manual; tensor-parallel sharding of the weights *inside* each stage stays
  GSPMD-automatic, so pp composes with tp/dp without hand-written psums.
- The decode batch splits into M microbatches that flow through stages in
  the classic GPipe schedule: at step t, stage s processes microbatch
  ``t - s``; activations hop stage→stage+1 via ``lax.ppermute`` over ICI.
  ``M + pp - 1`` steps drain the pipeline; with M ≥ pp every stage is busy
  in steady state. Out-of-range steps run with ``active=False`` so their KV
  writes sink to scratch block 0 (the allocator never hands out block 0).

The schedule is a ``lax.fori_loop`` — one compiled step body regardless of
microbatch count, XLA-friendly (no Python unrolling).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.models.llama import (
    decode_layer_scan,
    decode_targets,
    rms_norm,
    scatter_kv_rows,
)


def pipelined_decode(
    params,
    config: ModelConfig,
    k_cache: jax.Array,  # [L, N, BS, KVH*HD], layer axis sharded over pp
    v_cache: jax.Array,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, max_blocks]
    active: jax.Array,  # [B] bool
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for a batch, pipelined over the ``pp`` mesh axis.

    Same contract as ``llama.decode``: returns (logits [B, V] f32, k_cache,
    v_cache). Requires ``B % num_microbatches == 0`` (default M = pp)."""
    from dynamo_tpu.engine.config import resolve_moe_dispatch

    c = resolve_moe_dispatch(config, mesh.shape.get("ep", 1))
    pp = mesh.shape["pp"]
    B = tokens.shape[0]
    M = num_microbatches or pp
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    if c.num_layers % pp != 0:
        raise ValueError(f"num_layers {c.num_layers} not divisible by pp {pp}")
    mb = B // M
    bs = c.block_size
    max_blocks = block_tables.shape[1]

    poss_mb = positions.reshape(M, mb)
    tables_mb = block_tables.reshape(M, mb, max_blocks)
    act_mb = active.reshape(M, mb)

    embed = params["embed"]
    final_norm = params["final_norm"]
    tied = "lm_head" not in params
    head = embed if tied else params["lm_head"]
    layers = params["layers"]

    # Embed all microbatches once, outside the pipeline body: the embedding
    # table is tp-sharded over the vocab, so the gather (and its collective)
    # runs once under GSPMD instead of on every stage at every step.
    h0_mb = embed.at[tokens.reshape(M, mb)].get(mode="clip")  # [M, mb, D]

    def body(layers, kc, vc, h0, poss, tables, act):
        stage = lax.axis_index("pp")
        last = pp - 1

        def step(t, state):
            h_prev, kc, vc, out = state
            mb_idx = t - stage
            in_range = (mb_idx >= 0) & (mb_idx < M)
            i = jnp.clip(mb_idx, 0, M - 1)

            poss_i = jnp.take(poss, i, axis=0)
            tables_i = jnp.take(tables, i, axis=0)  # [mb, max_blocks]
            act_i = jnp.take(act, i, axis=0) & in_range

            # Stage 0 feeds its current microbatch's embeddings; later stages
            # consume the activation that arrived from the previous stage.
            h_in = jnp.where(stage == 0, jnp.take(h0, i, axis=0), h_prev)

            tgt_blocks, tgt_offs, mask = decode_targets(poss_i, tables_i, act_i, bs)

            h_out, k_rows, v_rows = decode_layer_scan(
                layers, c, kc, vc, h_in, poss_i, tables_i, mask, active=act_i,
            )
            kc, vc = scatter_kv_rows(kc, vc, k_rows, v_rows, tgt_blocks, tgt_offs)

            # Only the last stage's output is real; collect hidden states
            # ([mb, D], cheap) — the lm-head matmul runs once after the loop,
            # not V-wide on every stage every step.
            write = ((stage == last) & in_range).astype(h_out.dtype)
            out = out.at[i].set(out[i] * (1.0 - write) + h_out * write)

            h_next = lax.ppermute(h_out, "pp", [(s, (s + 1) % pp) for s in range(pp)])
            return (h_next, kc, vc, out)

        init = (
            jnp.zeros((mb, c.hidden_size), dtype=h0.dtype),
            kc, vc,
            jnp.zeros((M, mb, c.hidden_size), dtype=h0.dtype),
        )
        _, kc, vc, out = lax.fori_loop(0, M + pp - 1, step, init)
        # out is populated only on the last stage; exactly one stage
        # contributes, so the psum is an exact broadcast over pp. The f32
        # cast routes around an XLA-CPU crash on bf16 all-reduce
        # ("Invalid binary instruction opcode copy") and is harmless on TPU.
        out = lax.psum(jnp.where(stage == last, 1.0, 0.0) * out.astype(jnp.float32), "pp")
        return out.astype(h0.dtype), kc, vc

    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("pp"), P("pp"), P("pp"), P(), P(), P(), P()),
        out_specs=(P(), P("pp"), P("pp")),
        axis_names={"pp"},
        check_vma=False,
    )
    out, k_new, v_new = sharded(
        layers, k_cache, v_cache, h0_mb,
        poss_mb, tables_mb, act_mb,
    )
    # Final norm + lm head outside the pipeline body: the head weight is
    # tp-sharded, so GSPMD partitions this one matmul over tp.
    hl = rms_norm(out.reshape(B, c.hidden_size), final_norm, c.rms_norm_eps)
    logits = (hl @ (head.T if tied else head)).astype(jnp.float32)
    return logits, k_new, v_new
