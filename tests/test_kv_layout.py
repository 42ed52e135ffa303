"""The paged KV pool's layout contract (``KvCacheArrays``), held on the step
programs' jaxprs: the pool is stored ``[L, N, BS, KVH*HD]`` — the page the
attention kernels read — and no step program may re-lay it. Any ``reshape``,
``transpose`` or ``copy`` of an array of the pool's element count that does
not keep the operand's trailing two dimensions fails (the leading-dimension
merge ``[L, N, ...] -> [L*N, ...]`` passes: it moves no element to another
tile). The CPU lays arrays out differently, so this guards the shape contract;
a chip trace guards the time."""

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays, QuantKv, layer_flat, split_heads
from dynamo_tpu.engine.models import llama

CFG = get_config("tiny")
MLA = get_config("tiny-mla")  # latent row: kv_lora_rank 32 + rope 8
NUM_BLOCKS = 23  # the pool's element counts (23552; scales 1472) match no other array of a step
RELAYOUT = {"reshape", "transpose", "copy", "copy_p"}


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for item in val if isinstance(val, (tuple, list)) else (val,):
            inner = getattr(item, "jaxpr", item)  # ClosedJaxpr -> Jaxpr
            if hasattr(inner, "eqns"):
                yield inner


def pool_relayouts(jaxpr, pool_sizes):
    """Every equation under ``jaxpr`` (scan and while bodies, branches, nested
    jits, shard_map and kernel bodies) that re-lays an array of the pool's size."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in RELAYOUT:
            src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
            if src.size in pool_sizes and (
                eqn.primitive.name.startswith("copy") or src.shape[-2:] != dst.shape[-2:]
            ):
                found.append(f"{eqn.primitive.name}: {src.shape} -> {dst.shape}")
        for inner in _sub_jaxprs(eqn):
            found.extend(pool_relayouts(inner, pool_sizes))
    return found


def _pool(cfg):
    cache = KvCacheArrays.create(cfg, NUM_BLOCKS, dtype=jnp.float32)
    sizes = {leaf.size for leaf in jax.tree.leaves(cache.k)}
    return cache.k, cache.v, sizes


def _prefill(cfg, params, k, v):
    return lambda k, v: llama.prefill(
        params, cfg, k, v, jnp.arange(1, 17, dtype=jnp.int32), jnp.int32(12), jnp.int32(16),
        jnp.array([1, 2, 0, 0], jnp.int32),
    )


def _mixed_step(cfg, params, k, v):
    return lambda k, v: llama.mixed_step(
        params, cfg, k, v, jnp.arange(1, 17, dtype=jnp.int32), jnp.int32(12), jnp.int32(16),
        jnp.array([1, 2, 0, 0], jnp.int32), jnp.array([5, 6, 0], jnp.int32), jnp.array([20, 7, 0], jnp.int32),
        jnp.array([[3, 4, 0, 0], [5, 0, 0, 0], [0, 0, 0, 0]], jnp.int32), jnp.array([True, True, False]),
    )


def _decode_multi(cfg, params, k, v):
    return lambda k, v: llama.decode_multi(
        params, cfg, k, v, jnp.array([5, 6, 0], jnp.int32), jnp.array([20, 7, 0], jnp.int32),
        jnp.array([[3, 4, 0, 0], [5, 0, 0, 0], [0, 0, 0, 0]], jnp.int32), jnp.array([True, True, False]),
        jnp.zeros((3,), jnp.float32), jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.float32),
        jax.random.PRNGKey(0), 4,
    )


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"], ids=["bf16kv", "int8kv"])
@pytest.mark.parametrize("impl", ["megakernel", "gather"])
@pytest.mark.parametrize("program", [_prefill, _mixed_step, _decode_multi], ids=lambda f: f.__name__.strip("_"))
def test_step_program_never_relays_the_pool(program, impl, kv_dtype):
    cfg = CFG.replace(attention_impl=impl, kv_cache_dtype=kv_dtype)
    params = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
    k, v, sizes = _pool(cfg)
    assert isinstance(k, QuantKv) == (kv_dtype == "int8")
    jaxpr = jax.make_jaxpr(program(cfg, params, k, v))(k, v)
    if impl == "megakernel":
        assert "pallas_call" in str(jaxpr), "the kernel path is not in the program"
    assert pool_relayouts(jaxpr.jaxpr, sizes) == []


@pytest.mark.parametrize(
    "relayout",
    [
        lambda k: split_heads(k, CFG.num_kv_heads),  # the old layout's reshape
        lambda k: jnp.swapaxes(k, 2, 3),
        lambda k: jnp.array(k, copy=True),
        lambda k: jax.lax.scan(lambda c, _: (c, split_heads(layer_flat(k), CFG.num_kv_heads)[0, 0]), 0, None, length=2),
    ],
    ids=["split-heads", "transpose", "copy", "inside-scan"],
)
def test_the_walk_finds_a_relayout(relayout):
    k, _, sizes = _pool(CFG)
    assert pool_relayouts(jax.make_jaxpr(relayout)(k).jaxpr, sizes)
    assert pool_relayouts(jax.make_jaxpr(layer_flat)(k).jaxpr, sizes) == []


@pytest.mark.parametrize(
    "cfg,lanes,kv_heads",
    [
        (CFG, CFG.num_kv_heads * CFG.head_dim, CFG.num_kv_heads),
        (CFG.replace(kv_cache_dtype="int8"), CFG.num_kv_heads * CFG.head_dim, CFG.num_kv_heads),
        (MLA, 40, 1),
        (MLA.replace(kv_cache_dtype="int8"), 40, 1),
    ],
    ids=["bf16kv", "int8kv", "mla", "mla-int8kv"],
)
def test_pool_is_allocated_in_the_kernels_layout(cfg, lanes, kv_heads):
    cache = KvCacheArrays.create(cfg, NUM_BLOCKS, dtype=jnp.float32)
    rows = (cfg.num_layers, NUM_BLOCKS, cfg.block_size)
    assert cache.k.shape == (*rows, lanes) and cache.kv_heads == kv_heads
    if isinstance(cache.k, QuantKv):
        assert cache.k.q.dtype == jnp.int8 and cache.k.scale.shape == (*rows, kv_heads)
    assert layer_flat(cache.k).shape == (cfg.num_layers * NUM_BLOCKS, cfg.block_size, lanes)
