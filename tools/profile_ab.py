"""Interleaved A/B decode profiling — robust to drifting chip performance.

Runs each variant in round-robin rounds and reports per-round times + the
median, so variant deltas are comparable even when the chip's absolute
speed drifts between rounds.
"""

from __future__ import annotations

import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.kv_cache import KvCacheArrays
from dynamo_tpu.engine.models import llama


def main():
    model = os.environ.get("BENCH_MODEL", "llama-3.2-1b")
    B = int(os.environ.get("BENCH_BATCH", "8"))
    ctx = int(os.environ.get("BENCH_CTX", "1024"))
    rounds = int(os.environ.get("BENCH_ROUNDS", "5"))
    iters = int(os.environ.get("BENCH_ITERS", "30"))
    cfg = get_config(model).replace(max_seq_len=2048)
    num_blocks = B * (ctx // cfg.block_size + 4) + 8

    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)

    needed = (ctx + 64) // cfg.block_size
    width = min((needed + 15) // 16 * 16, cfg.max_seq_len // cfg.block_size)
    tables_np = np.zeros((B, width), dtype=np.int32)
    for i in range(B):
        tables_np[i, :needed] = (np.arange(needed) + 1 + i * needed) % (num_blocks - 1) + 1
    tables = jnp.asarray(tables_np)
    active = jnp.ones((B,), dtype=bool)
    toks = jnp.zeros((B,), dtype=jnp.int32)
    pos = jnp.full((B,), ctx, dtype=jnp.int32)

    variants = {}

    def add_decode_variant(name, impl):
        c = cfg.replace(attention_impl=impl)
        step = jax.jit(
            lambda p, k, v: llama.decode(p, c, k, v, toks, pos, tables, active),
            donate_argnums=(1, 2),
        )
        cache = KvCacheArrays.create(cfg, num_blocks=num_blocks, dtype=jnp.bfloat16)
        state = {"k": cache.k, "v": cache.v}

        def run_once():
            logits, state["k"], state["v"] = step(params, state["k"], state["v"])
            return logits

        variants[name] = run_once

    add_decode_variant("gather", "gather")

    # Weights-only floor (no cache, no attention reads).
    def make_floor():
        def floor_fn(p, t):
            h = p["embed"].at[t].get(mode="clip")

            def layer_fn(h, lp):
                x = llama.rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                q = x @ lp["wq"]
                kk = x @ lp["wk"]
                vv = x @ lp["wv"]
                a = q + jnp.concatenate([kk, vv, kk, vv], axis=-1) * 0
                h = h + a @ lp["wo"]
                x = llama.rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
                h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
                return h, None

            h, _ = jax.lax.scan(layer_fn, h, p["layers"])
            h = llama.rms_norm(h, p["final_norm"], cfg.rms_norm_eps)
            return (h @ p["embed"].T).astype(jnp.float32)

        f = jax.jit(floor_fn)

        def run_once():
            return f(params, toks)

        return run_once

    variants["floor"] = make_floor()

    # Warmup all.
    for name, fn in variants.items():
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        print(f"warmup {name}: {time.perf_counter()-t0:.1f}s", flush=True)

    results = {name: [] for name in variants}
    for r in range(rounds):
        for name, fn in variants.items():
            out = fn()
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            ms = (time.perf_counter() - t0) / iters * 1000
            results[name].append(ms)
            print(f"round {r} {name:8s}: {ms:7.3f} ms", flush=True)

    for name, times in results.items():
        med = statistics.median(times)
        print(f"{name:8s}: med {med:7.3f} ms   rounds: " + " ".join(f"{t:6.2f}" for t in times))


if __name__ == "__main__":
    main()
