"""Tensor-parallel sharding tests on the virtual 8-device CPU mesh: sharded
prefill/decode must match single-device results (GSPMD inserts the
collectives; correctness is what we assert here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.sharding import (
    ParallelConfig,
    build_mesh,
    kv_cache_spec,
    param_specs,
    shard_params,
)

CFG = get_config("tiny").replace(dtype="float32")


def test_mesh_axes():
    mesh = build_mesh(ParallelConfig(tp=4, dp=2))
    assert mesh.shape == {"dp": 2, "pp": 1, "sp": 1, "ep": 1, "tp": 4}


def test_param_specs_cover_params():
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    specs = param_specs(CFG.tie_word_embeddings)
    # Same tree structure — zip must not error.
    jax.tree.map(lambda a, b: None, params, specs, is_leaf=lambda x: isinstance(x, (jax.Array, P)))


def test_tp_prefill_decode_matches_single_device():
    mesh = build_mesh(ParallelConfig(tp=2))
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

    tokens = list(range(40, 60))
    T = len(tokens)
    block_table = jnp.array([1, 2, 3, 0], dtype=jnp.int32)
    padded = jnp.array(tokens + [0] * (32 - T), dtype=jnp.int32)

    # Single-device reference.
    cache = KvCacheArrays.create(CFG, 16, dtype=jnp.float32)
    ref_logits, ref_k, ref_v = llama.prefill(
        params, CFG, cache.k, cache.v, padded, jnp.int32(T), jnp.int32(0), block_table
    )

    # Sharded run: params TP-sharded, cache sharded over kv heads.
    sp = shard_params(params, mesh, CFG.tie_word_embeddings)
    cache_sharding = NamedSharding(mesh, kv_cache_spec(CFG.num_kv_heads, 2))
    k_sh = jax.device_put(jnp.zeros_like(cache.k), cache_sharding)
    v_sh = jax.device_put(jnp.zeros_like(cache.v), cache_sharding)

    logits, k_sh, v_sh = jax.jit(
        lambda p, k, v, t: llama.prefill(p, CFG, k, v, t, jnp.int32(T), jnp.int32(0), block_table)
    )(sp, k_sh, v_sh, padded)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-4)

    # Decode one step sharded vs reference.
    next_tok = int(jnp.argmax(ref_logits))
    B = 2
    toks = jnp.zeros((B,), dtype=jnp.int32).at[0].set(next_tok)
    positions = jnp.zeros((B,), dtype=jnp.int32).at[0].set(T)
    tables = jnp.zeros((B, 4), dtype=jnp.int32).at[0].set(block_table)
    active = jnp.zeros((B,), dtype=bool).at[0].set(True)

    ref_dec, _, _ = llama.decode(params, CFG, ref_k, ref_v, toks, positions, tables, active)
    dec, _, _ = jax.jit(lambda p, k, v: llama.decode(p, CFG, k, v, toks, positions, tables, active))(
        sp, k_sh, v_sh
    )
    np.testing.assert_allclose(np.asarray(dec[0]), np.asarray(ref_dec[0]), rtol=1e-4, atol=1e-4)


def test_tp4_with_dp2_mesh_compiles():
    """Full 8-device mesh (dp=2, tp=4): sharded decode step compiles and runs."""
    cfg = CFG.replace(num_heads=8, num_kv_heads=4, head_dim=8)
    mesh = build_mesh(ParallelConfig(tp=4, dp=2))
    params = llama.init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    sp = shard_params(params, mesh, cfg.tie_word_embeddings)
    cache_sharding = NamedSharding(mesh, kv_cache_spec(cfg.num_kv_heads, 4))
    cache = KvCacheArrays.create(cfg, 16, dtype=jnp.float32, sharding=cache_sharding)

    B = 4
    toks = jnp.arange(B, dtype=jnp.int32)
    positions = jnp.zeros((B,), dtype=jnp.int32)
    tables = jnp.ones((B, 4), dtype=jnp.int32)
    active = jnp.ones((B,), dtype=bool)
    logits, _, _ = jax.jit(lambda p, k, v: llama.decode(p, cfg, k, v, toks, positions, tables, active))(
        sp, cache.k, cache.v
    )
    assert logits.shape == (B, cfg.vocab_size)


@pytest.mark.parametrize(
    "attention_impl,use_flash,kv_dtype",
    [("megakernel", False, "auto"), ("megakernel", False, "int8"),
     ("gather", True, "auto"), ("paged", False, "auto")],
)
def test_tp_pallas_kernels_partition_over_heads(attention_impl, use_flash, kv_dtype):
    """The Pallas attention kernels (interpreted here) run per tp shard under
    ``jax.shard_map`` when the model is traced under an engine's mesh
    (``bind_mesh``): prefill + one decode step match the one-device run of
    the same kernels."""
    from dynamo_tpu.engine.sharding import bind_mesh

    cfg = CFG.replace(attention_impl=attention_impl, kv_cache_dtype=kv_dtype)
    mesh = build_mesh(ParallelConfig(tp=2))
    model = bind_mesh(llama, mesh)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    T = 20
    padded = jnp.array(list(range(40, 40 + T)) + [0] * (32 - T), dtype=jnp.int32)
    block_table = jnp.array([1, 2, 3, 0], dtype=jnp.int32)
    toks = jnp.array([7, 0], dtype=jnp.int32)
    positions = jnp.array([T, 0], dtype=jnp.int32)
    tables = jnp.zeros((2, 4), dtype=jnp.int32).at[0].set(block_table)
    active = jnp.array([True, False])

    def run(m, p, k, v):
        step = jax.jit(
            lambda p, k, v: m.prefill(
                p, cfg, k, v, padded, jnp.int32(T), jnp.int32(0), block_table,
                use_flash=use_flash, has_prefix=False,
            )
        )
        logits, k, v = step(p, k, v)
        dec, _, _ = jax.jit(
            lambda p, k, v: m.decode(p, cfg, k, v, toks, positions, tables, active)
        )(p, k, v)
        return np.asarray(logits), np.asarray(dec[0])

    cache = KvCacheArrays.create(cfg, 16, dtype=jnp.float32)
    ref = run(llama, params, cache.k, cache.v)
    sh_cache = KvCacheArrays.create(
        cfg, 16, dtype=jnp.float32,
        sharding=NamedSharding(mesh, kv_cache_spec(cfg.num_kv_heads, 2)),
    )
    got = run(model, shard_params(params, mesh, cfg.tie_word_embeddings), sh_cache.k, sh_cache.v)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_tp_indivisible_kv_heads_take_xla_paths():
    """KV heads that do not divide by tp replicate the cache; the kernels
    cannot partition, so the resolution — observed from the mesh — is the
    XLA gather and XLA prefill, not an error at first compile."""
    from dynamo_tpu.engine.sharding import bind_mesh

    cfg = CFG.replace(attention_impl="megakernel", prefill_impl="flash")
    model = bind_mesh(llama, build_mesh(ParallelConfig(tp=4)))  # tiny: 2 KV heads
    assert model.resolve_attention_impl(cfg, None) == "gather"
    assert model.resolve_prefill_impl(cfg) == "xla"
    assert llama.resolve_attention_impl(cfg, None) == "megakernel"
    assert llama.resolve_prefill_impl(cfg) == "flash"
