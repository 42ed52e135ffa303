"""Isolate the paged-KV cache cost in the decode step.

Variants (all with the real weights scan + lm_head):
- noscatter_nokernel : no cache write, no attention read (≈ no_attn floor)
- scatter_only       : cache write into stacked [L,...] carry, no read
- kernel_noscatter   : kernel attention read, no cache write
- kernel_full        : current full path (scatter + kernel)
- list_full_gather   : per-layer cache LIST (unrolled loop), scatter + gather
- list_full_kernel   : per-layer cache LIST (unrolled loop), scatter + kernel
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Run as a file from a bare checkout: the package sits one directory up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.models import llama


def bench_step(step, args, donate_ids, iters=50):
    """step(*args) -> (logits, k, v) with k,v donated and threaded."""
    args = list(args)
    out = step(*args)
    jax.block_until_ready(out)
    for slot, res in zip(donate_ids, out[1:]):
        args[slot] = res
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(*args)
        logits = out[0]
        for slot, res in zip(donate_ids, out[1:]):
            args[slot] = res
    jax.block_until_ready(logits)
    return (time.perf_counter() - t0) / iters * 1000


def main():
    model = os.environ.get("BENCH_MODEL", "llama-3.2-1b")
    B = int(os.environ.get("BENCH_BATCH", "8"))
    ctx = int(os.environ.get("BENCH_CTX", "1024"))
    cfg = get_config(model).replace(max_seq_len=2048)
    c = cfg
    num_blocks = B * (ctx // cfg.block_size + 4) + 8
    L = cfg.num_layers

    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    kshape = (L, num_blocks, cfg.block_size, cfg.num_kv_heads, cfg.head_dim)
    k_cache = jnp.zeros(kshape, dtype=jnp.bfloat16)
    v_cache = jnp.zeros(kshape, dtype=jnp.bfloat16)

    needed = (ctx + 64) // cfg.block_size
    width = min((needed + 15) // 16 * 16, cfg.max_seq_len // cfg.block_size)
    tables = np.zeros((B, width), dtype=np.int32)
    for i in range(B):
        tables[i, :needed] = (np.arange(needed) + 1 + i * needed) % (num_blocks - 1) + 1
    tables = jnp.asarray(tables)
    active = jnp.ones((B,), dtype=bool)
    toks = jnp.zeros((B,), dtype=jnp.int32)
    pos = jnp.full((B,), ctx, dtype=jnp.int32)

    def make_scan_variant(do_scatter: bool, attn: str):
        def step(p, kc, vc, t, po, tbl):
            h = p["embed"].at[t].get(mode="clip")
            tgt_blocks, tgt_offs, mask = llama.decode_targets(po, tbl, active, c.block_size)
            kv_lens = jnp.where(active, po + 1, 0)

            def layer_fn(carry, xs):
                h, kc, vc = carry
                lp, l = xs
                x = llama.rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
                q = (x @ lp["wq"]).reshape(B, 1, c.num_heads, c.head_dim)
                k = (x @ lp["wk"]).reshape(B, 1, c.num_kv_heads, c.head_dim)
                v = (x @ lp["wv"]).reshape(B, 1, c.num_kv_heads, c.head_dim)
                q = llama.apply_rope(q, po[:, None], c.rope_theta)[:, 0]
                k = llama.apply_rope(k, po[:, None], c.rope_theta)[:, 0]
                v = v[:, 0]
                if do_scatter:
                    kc = kc.at[l, tgt_blocks, tgt_offs].set(k)
                    vc = vc.at[l, tgt_blocks, tgt_offs].set(v)
                kl = lax.dynamic_index_in_dim(kc, l, 0, keepdims=False)
                vl = lax.dynamic_index_in_dim(vc, l, 0, keepdims=False)
                if attn == "gather":
                    ctxlen = tbl.shape[1] * c.block_size
                    k_ctx = kl[tbl].reshape(B, ctxlen, c.num_kv_heads, c.head_dim)
                    v_ctx = vl[tbl].reshape(B, ctxlen, c.num_kv_heads, c.head_dim)
                    a = jax.vmap(lambda qb, kb, vb, mb: llama._attend(qb[None], kb, vb, mb[None], c)[0])(
                        q, k_ctx, v_ctx, mask)
                else:
                    a = q
                h = h + a.reshape(B, c.q_size) @ lp["wo"]
                x = llama.rms_norm(h, lp["mlp_norm"], c.rms_norm_eps)
                h = h + llama._mlp(x, lp, c)
                return (h, kc, vc), None

            (h, kc, vc), _ = lax.scan(layer_fn, (h, kc, vc),
                                      (p["layers"], jnp.arange(c.num_layers, dtype=jnp.int32)))
            h = llama.rms_norm(h, p["final_norm"], c.rms_norm_eps)
            logits = h @ p["embed"].T
            return logits.astype(jnp.float32), kc, vc

        return jax.jit(step, donate_argnums=(1, 2))

    for name, (scat, attn) in {
        "noscatter_noattn": (False, "none"),
        "scatter_only": (True, "none"),
        "kernel_noscatter": (False, "kernel"),
        "kernel_full": (True, "kernel"),
        "gather_full": (True, "gather"),
    }.items():
        step = make_scan_variant(scat, attn)
        ms = bench_step(step, (params, jnp.copy(k_cache), jnp.copy(v_cache), toks, pos, tables), (1, 2))
        print(f"{name:18s}: {ms:7.3f} ms")

    # --- per-layer LIST cache, unrolled python loop ---
    k_list = [jnp.zeros(kshape[1:], dtype=jnp.bfloat16) for _ in range(L)]
    v_list = [jnp.zeros(kshape[1:], dtype=jnp.bfloat16) for _ in range(L)]

    def make_list_variant(attn: str):
        def step(p, ks, vs, t, po, tbl):
            h = p["embed"].at[t].get(mode="clip")
            tgt_blocks, tgt_offs, mask = llama.decode_targets(po, tbl, active, c.block_size)
            kv_lens = jnp.where(active, po + 1, 0)
            ks_out, vs_out = [], []
            for l in range(L):
                lp = {k2: v2[l] for k2, v2 in p["layers"].items()}
                x = llama.rms_norm(h, lp["attn_norm"], c.rms_norm_eps)
                q = (x @ lp["wq"]).reshape(B, 1, c.num_heads, c.head_dim)
                k = (x @ lp["wk"]).reshape(B, 1, c.num_kv_heads, c.head_dim)
                v = (x @ lp["wv"]).reshape(B, 1, c.num_kv_heads, c.head_dim)
                q = llama.apply_rope(q, po[:, None], c.rope_theta)[:, 0]
                k = llama.apply_rope(k, po[:, None], c.rope_theta)[:, 0]
                v = v[:, 0]
                kl = ks[l].at[tgt_blocks, tgt_offs].set(k)
                vl = vs[l].at[tgt_blocks, tgt_offs].set(v)
                ks_out.append(kl)
                vs_out.append(vl)
                if attn == "gather":
                    ctxlen = tbl.shape[1] * c.block_size
                    k_ctx = kl[tbl].reshape(B, ctxlen, c.num_kv_heads, c.head_dim)
                    v_ctx = vl[tbl].reshape(B, ctxlen, c.num_kv_heads, c.head_dim)
                    a = jax.vmap(lambda qb, kb, vb, mb: llama._attend(qb[None], kb, vb, mb[None], c)[0])(
                        q, k_ctx, v_ctx, mask)
                h = h + a.reshape(B, c.q_size) @ lp["wo"]
                x = llama.rms_norm(h, lp["mlp_norm"], c.rms_norm_eps)
                h = h + llama._mlp(x, lp, c)
            h = llama.rms_norm(h, p["final_norm"], c.rms_norm_eps)
            logits = h @ p["embed"].T
            return (logits.astype(jnp.float32), ks_out, vs_out)

        return jax.jit(step, donate_argnums=(1, 2))

    for name, attn in {"list_kernel": "kernel", "list_gather": "gather"}.items():
        step = make_list_variant(attn)
        ks = [jnp.copy(x) for x in k_list]
        vs = [jnp.copy(x) for x in v_list]
        out = step(params, ks, vs, toks, pos, tables)
        ks, vs = out[1], out[2]
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        iters = 50
        for _ in range(iters):
            logits, ks, vs = step(params, ks, vs, toks, pos, tables)
        jax.block_until_ready(logits)
        ms = (time.perf_counter() - t0) / iters * 1000
        print(f"{name:18s}: {ms:7.3f} ms")


if __name__ == "__main__":
    main()
