"""A/B the prefill attention implementations on the real chip.

Usage: python tools/bench_prefill_impl.py [model] [prompt_len]
Times one full prefill dispatch (cache donated per call) for the XLA path
vs the Pallas flash path, at table widths the scheduler would pass.
"""

import os
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

model = sys.argv[1] if len(sys.argv) > 1 else "llama-3.2-1b"
prompt_len = int(sys.argv[2]) if len(sys.argv) > 2 else 2048

cfg = get_config(model).replace(max_seq_len=max(4096, prompt_len + 512))
params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
flops = 2 * (pbytes / 2) * prompt_len

num_blocks = prompt_len // cfg.block_size + 8
toks = jnp.arange(prompt_len, dtype=jnp.int32) % 1000

# Table width: power-of-two bucket covering the prompt (what the scheduler
# passes) — NOT max_blocks_per_seq.
w = 16
while w < prompt_len // cfg.block_size + 1:
    w *= 2
table = jnp.asarray(np.pad(np.arange(1, num_blocks, dtype=np.int32), (0, max(0, w - num_blocks + 1)))[:w])


def run(use_flash, label):
    cache = KvCacheArrays.create(cfg, num_blocks=num_blocks, dtype=jnp.bfloat16)
    fn = jax.jit(
        lambda p, k, v, t: llama.prefill(
            p, cfg, k, v, t, jnp.int32(prompt_len), jnp.int32(0), table,
            use_flash=use_flash, has_prefix=False,
        ),
        donate_argnums=(1, 2),
    )
    k, v = cache.k, cache.v
    logits, k, v = fn(params, k, v, toks)
    np.asarray(logits[:4])  # sync: the readback waits for the device
    iters = 16
    t0 = time.perf_counter()
    for _ in range(iters):
        logits, k, v = fn(params, k, v, toks)
    np.asarray(logits[:4])
    dt = (time.perf_counter() - t0) / iters
    print(f"{label}: {dt*1e3:.2f} ms  ({prompt_len/dt:.0f} tok/s, mfu {flops/dt/1e12/197*100:.1f}%)")
    return dt


run(False, "xla  (pow2 table)")
run(True, "flash(pow2 table)")
