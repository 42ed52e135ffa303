"""Numerics parity: Pallas flash prefill (interpret mode on CPU) vs the XLA
reference path. Covers fresh prefills (no prefix piece), chunked prefills
with a cached prefix (online-softmax merge), padded buckets, and GQA.
Ref role: the engines' FlashAttention prefill kernels (SURVEY.md §1 L5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import llama


def _mk(config, seed=0):
    params = llama.init_params(config, jax.random.PRNGKey(seed), dtype=jnp.float32)
    cache = KvCacheArrays.create(config, num_blocks=32, dtype=jnp.float32)
    return params, cache


def _tokens(n, vocab, seed=1):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab))


@pytest.mark.parametrize("valid", [64, 50])
def test_fresh_prefill_parity(valid):
    """cache_len=0 path: kernel-only attention must match the XLA path."""
    c = get_config("tiny")
    params, cache = _mk(c)
    T = 64
    toks = np.zeros((T,), np.int32)
    toks[:valid] = _tokens(valid, c.vocab_size)
    table = jnp.asarray(np.arange(1, 5, dtype=np.int32).repeat(1))
    args = (
        jnp.asarray(toks),
        jnp.int32(valid),
        jnp.int32(0),
        jnp.pad(table, (0, 12)),
    )
    ref, kr, vr = llama.prefill(params, c, cache.k, cache.v, *args, use_flash=False)
    out, kf, vf = llama.prefill(params, c, cache.k, cache.v, *args, use_flash=True, has_prefix=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    # Cache contents written identically.
    np.testing.assert_allclose(np.asarray(kf), np.asarray(kr), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vr), rtol=2e-4, atol=2e-4)


def test_chunked_prefill_prefix_merge_parity():
    """Second chunk attends [cached prefix ; chunk] — the merge path."""
    c = get_config("tiny")
    params, _ = _mk(c)
    total, first = 96, 64
    toks = _tokens(total, c.vocab_size)
    table = jnp.asarray(np.pad(np.arange(1, 8, dtype=np.int32), (0, 9)))

    def run(use_flash):
        cache = KvCacheArrays.create(c, num_blocks=32, dtype=jnp.float32)
        k, v = cache.k, cache.v
        t0 = np.zeros((64,), np.int32)
        t0[:first] = toks[:first]
        _, k, v = llama.prefill(
            params, c, k, v, jnp.asarray(t0), jnp.int32(first), jnp.int32(0), table,
            use_flash=use_flash, has_prefix=False,
        )
        t1 = np.zeros((32,), np.int32)
        t1[: total - first] = toks[first:]
        logits, k, v = llama.prefill(
            params, c, k, v, jnp.asarray(t1), jnp.int32(total - first), jnp.int32(first), table,
            use_flash=use_flash, has_prefix=True,
        )
        return logits, k, v

    ref, kr, vr = run(False)
    out, kf, vf = run(True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(kf), np.asarray(kr), rtol=2e-4, atol=2e-4)


def test_all_logits_parity():
    """Spec-decode verification surface (all_logits=True) under flash."""
    c = get_config("tiny")
    params, cache = _mk(c)
    T, valid = 32, 20
    toks = np.zeros((T,), np.int32)
    toks[:valid] = _tokens(valid, c.vocab_size)
    table = jnp.asarray(np.pad(np.arange(1, 4, dtype=np.int32), (0, 13)))
    args = (jnp.asarray(toks), jnp.int32(valid), jnp.int32(0), table)
    ref, _, _ = llama.prefill(params, c, cache.k, cache.v, *args, all_logits=True, use_flash=False)
    out, _, _ = llama.prefill(
        params, c, cache.k, cache.v, *args, all_logits=True, use_flash=True, has_prefix=False
    )
    np.testing.assert_allclose(
        np.asarray(out)[:valid], np.asarray(ref)[:valid], rtol=5e-4, atol=5e-4
    )


def test_scheduler_flash_prefill_e2e():
    """Scheduler with prefill_impl="flash" (interpreted kernel) produces the
    same greedy tokens as the XLA path."""
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

    prompt = list(_tokens(40, 256, seed=7))

    def run(impl):
        c = get_config("tiny").replace(prefill_impl=impl)
        params = llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.float32)
        sched = Scheduler(c, params, SchedulerConfig(num_blocks=64), dtype=jnp.float32)
        seq = sched.add_request(
            "r1", [int(t) for t in prompt], SamplingParams(temperature=0.0),
            StopConditions(max_tokens=8),
        )
        for _ in range(40):
            sched.step()
            if seq.state.value == "finished":
                break
        return seq.output_ids

    assert run("flash") == run("xla")


@pytest.mark.parametrize("attention_impl,static", [("megakernel", False), ("paged", False), ("gather", True)])
def test_has_prefix_keys_an_executable_only_where_it_changes_the_program(attention_impl, static):
    """``has_prefix`` is a static argument of the prefill and mixed-step
    programs on the flash path's own chunk attention, which only the gather
    reaches; where a kernel serves the pool (``megakernel``, ``paged``) a
    chunk walks tiles and the programs never read it, so a chunk with a cached
    prefix reuses the executable the fresh chunk traced: nothing is built
    mid-traffic."""
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import Scheduler, SchedulerConfig, StopConditions

    c = get_config("tiny").replace(attention_impl=attention_impl, prefill_impl="flash")
    params = llama.init_params(c, jax.random.PRNGKey(0), dtype=jnp.float32)
    sched = Scheduler(c, params, SchedulerConfig(
        num_blocks=64, max_running=4, prefill_buckets=[32], decode_buckets=[4],
        max_prefill_chunk=32, mixed_prefill_budget=32, num_scheduler_steps=1,
        enable_prefix_caching=False,
    ), dtype=jnp.float32)
    assert sched._use_flash_prefill and sched._hp_static is static
    stop = StopConditions(max_tokens=6, ignore_eos=True)
    sched.add_request("a", [int(t) for t in _tokens(20, 256, seed=3)], SamplingParams(temperature=0.0), stop)
    sched.step()  # "a" prefills alone and decodes from here on
    # Two chunks: the second has a cached prefix, and both ride mixed steps beside "a".
    sched.add_request("b", [int(t) for t in _tokens(50, 256, seed=4)], SamplingParams(temperature=0.0), stop)
    for _ in range(40):
        if not sched.has_work():
            break
        sched.step()
    assert sched.mixed_steps_total >= 2
    assert sched._mixed_jits
    for fn in sched._mixed_jits.values():
        assert fn._cache_size() == (2 if static else 1)
    mixed_keys = [k for k in sched.flight._exec_keys if k[0] == "mixed"]
    assert {len(k) for k in mixed_keys} == {6 if static else 5}  # ("mixed", s, p_w, b, w[, has_prefix])
