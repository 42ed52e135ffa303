"""MLA (DeepSeek-style latent attention) family tests: decode-vs-prefill
consistency over the paged latent cache, engine e2e, cache sizing."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.engine import EngineArgs, TpuEngine
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import get_module, mla
from dynamo_tpu.engine.scheduler import SchedulerConfig
from dynamo_tpu.runtime.engine import Context

CFG = get_config("tiny-mla")


def test_dispatch():
    assert get_module(CFG) is mla
    assert get_module(get_config("tiny")).__name__.endswith("llama")


def test_latent_cache_shape():
    cache = KvCacheArrays.create(CFG, num_blocks=8, dtype=jnp.float32)
    # One latent row per token: kv_lora_rank + rope dim, a single "head" in
    # the pool's merged-lane layout.
    assert cache.k.shape == (2, 8, 16, 40)
    assert cache.kv_heads == 1
    assert cache.v.shape == (2, 1, 1, 1)


def test_decode_matches_prefill_logits():
    """Token t+1 logits from decode (after prefilling t tokens) must match
    prefilling t+1 tokens directly — same latent cache contract."""
    key = jax.random.PRNGKey(0)
    params = mla.init_params(CFG, key, dtype=jnp.float32)
    prompt = list(range(30, 45))
    T = len(prompt)
    bucket = 16
    n_blocks = 4
    cache = KvCacheArrays.create(CFG, num_blocks=8, dtype=jnp.float32)
    table = jnp.arange(1, 1 + n_blocks, dtype=jnp.int32)

    padded = jnp.zeros((bucket,), dtype=jnp.int32).at[:T].set(jnp.asarray(prompt))
    logits_p, k1, v1 = mla.prefill(
        params, CFG, cache.k, cache.v, padded, jnp.int32(T), jnp.int32(0), table
    )

    # Decode one token on top of the prefilled cache.
    next_tok = int(jnp.argmax(logits_p))
    logits_d, k2, _ = mla.decode(
        params, CFG, k1, v1,
        jnp.asarray([next_tok], dtype=jnp.int32),
        jnp.asarray([T], dtype=jnp.int32),
        table[None, :],
        jnp.ones((1,), dtype=bool),
    )

    # Reference: prefill the full T+1 sequence in a fresh cache.
    cache2 = KvCacheArrays.create(CFG, num_blocks=8, dtype=jnp.float32)
    full = prompt + [next_tok]
    padded2 = jnp.zeros((bucket,), dtype=jnp.int32).at[: T + 1].set(jnp.asarray(full))
    logits_ref, _, _ = mla.prefill(
        params, CFG, cache2.k, cache2.v, padded2, jnp.int32(T + 1), jnp.int32(0), table
    )
    np.testing.assert_allclose(np.asarray(logits_d[0]), np.asarray(logits_ref), rtol=2e-4, atol=2e-4)


def test_mla_engine_e2e():
    async def run():
        engine = TpuEngine.build(
            EngineArgs(
                model="tiny-mla",
                dtype="float32",
                scheduler=SchedulerConfig(
                    num_blocks=32, max_running=4, prefill_buckets=[16, 32], decode_buckets=[1, 2, 4]
                ),
            )
        )
        try:
            out = []
            async for frame in engine.generate(
                {"token_ids": list(range(10, 28)),
                 "sampling_options": {"temperature": 0.0},
                 "stop_conditions": {"max_tokens": 6}},
                Context(),
            ):
                out.extend(frame["token_ids"])
            assert len(out) == 6
            # Greedy determinism across a second request (prefix cache hit).
            out2 = []
            async for frame in engine.generate(
                {"token_ids": list(range(10, 28)),
                 "sampling_options": {"temperature": 0.0},
                 "stop_conditions": {"max_tokens": 6}},
                Context(),
            ):
                out2.extend(frame["token_ids"])
            assert out == out2
        finally:
            await engine.stop()

    asyncio.run(run())


def test_presets_construct():
    for name in ("deepseek-v2-lite", "deepseek-v3", "qwen2.5-7b", "mistral-7b"):
        cfg = get_config(name)
        assert cfg.architecture in ("llama", "mla")
        if cfg.architecture == "mla":
            assert cfg.kv_lora_rank > 0 and cfg.v_head_dim > 0
