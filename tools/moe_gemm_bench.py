#!/usr/bin/env python3
"""Micro-benchmark of one MoE layer's three grouped GEMMs out of a stacked
``[L, E, D, F]`` expert tree (PERF.md §6, PR 29): which form reads only the
visited experts of layer ``l``, and what it costs.

Forms, each a ``lax.scan`` (or an unrolled loop) over L layers of
route -> sort -> gate/up/down -> combine at T rows:

- ``scan``:   the expert stacks ride the scan's xs; ``lax.ragged_dot`` on the
              per-layer ``[E, D, F]`` slice (the program before PR 29).
- ``stack``:  the stacks closed over as ``[L*E, D, F]``; ``lax.ragged_dot``
              with ``L*E`` group sizes, zero outside layer l's E.
- ``gmm``:    the same operands through megablox ``gmm`` (several tilings).
- ``unroll``: a Python loop with static slices and ``lax.ragged_dot``.

    chiprun -- python tools/moe_gemm_bench.py            # Mixtral widths, L=3
    JAX_PLATFORMS=cpu python tools/moe_gemm_bench.py --tiny   # control flow only

Prints one JSON line per reading (milliseconds a layer, temporaries in
bytes) and writes them to ``chiprun_out/moe_gemm_bench.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax


def _dispatch(x, router, K, base, groups):
    """Rows sorted by expert: (xs [T*K, D], group_sizes [groups], tok, w)."""
    logits = (x @ router).astype(jnp.float32)
    vals, idx = lax.top_k(logits, K)
    w = jax.nn.softmax(vals, axis=-1).reshape(-1)
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e)
    tok = order // K
    sizes = jnp.bincount(flat_e + base, length=groups).astype(jnp.int32)
    return x[tok], sizes, tok, w[order].astype(x.dtype)


def _ffn(x, router, wg, wu, wd, K, base, dot):
    xs, sizes, tok, w = _dispatch(x, router, K, base, wg.shape[0])
    h = jax.nn.silu(dot(xs, wg, sizes)) * dot(xs, wu, sizes)
    y = dot(h, wd, sizes)
    return x + jnp.zeros_like(x).at[tok].add(y * w[:, None])


def _gmm_dot(tiling_in, tiling_out, interpret):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def dot(lhs, rhs, sizes):
        tiling = tiling_in if rhs.shape[1] <= rhs.shape[2] else tiling_out
        m = lhs.shape[0]
        tm = min(tiling[0], -(-m // 8) * 8)
        pad = -m % tm
        if pad:
            lhs = jnp.concatenate([lhs, jnp.zeros((pad, lhs.shape[1]), lhs.dtype)])
        out = gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=(tm,) + tuple(tiling[1:]), interpret=interpret)
        return out[:m]

    return dot


def build(form, L, E, K, dot=lax.ragged_dot):
    def scan(x, routers, wg, wu, wd):
        def body(h, xs):
            r, g, u, d = xs
            return _ffn(h, r, g, u, d, K, 0, dot), None

        return lax.scan(body, x, (routers, wg, wu, wd))[0]

    def stack(x, routers, wg, wu, wd):
        flat = [w.reshape((L * E,) + w.shape[2:]) for w in (wg, wu, wd)]

        def body(h, xs):
            r, l = xs
            return _ffn(h, r, *flat, K, l * E, dot), None

        return lax.scan(body, x, (routers, jnp.arange(L, dtype=jnp.int32)))[0]

    def unroll(x, routers, wg, wu, wd):
        for l in range(L):
            x = _ffn(x, routers[l], wg[l], wu[l], wd[l], K, 0, dot)
        return x

    return {"scan": scan, "stack": stack, "gmm": stack, "unroll": unroll}[form]


def measure(fn, args, iters):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    out = compiled(*args)
    out.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3, int(getattr(mem, "temp_size_in_bytes", 0)), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--rows", type=int, nargs="*", default=[32, 4, 288, 256])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--forms", nargs="*", default=["scan", "stack", "gmm", "unroll"])
    a = ap.parse_args()
    L, E, K = a.layers, 8, 2
    D, F = (128, 256) if a.tiny else (4096, 14336)
    dtype = jnp.float32 if a.tiny else jnp.bfloat16
    on_tpu = jax.devices()[0].platform == "tpu"
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    wg = jax.random.normal(keys[0], (L, E, D, F), dtype) * D**-0.5
    wu = jax.random.normal(keys[1], (L, E, D, F), dtype) * D**-0.5
    wd = jax.random.normal(keys[2], (L, E, F, D), dtype) * F**-0.5
    routers = jax.random.normal(keys[3], (L, D, E), dtype) * 8 * D**-0.5
    tilings = (
        [((128, 128, 128), (128, 128, 128))]
        if a.tiny
        else [
            ((128, 128, 128), (128, 128, 128)),
            ((128, 2048, 1024), (128, 2048, 1024)),
            ((128, 4096, 512), (128, 2048, 1024)),
            ((128, 1024, 2048), (128, 1024, 2048)),
            ((128, 512, 3584), (128, 512, 4096)),
            ((256, 2048, 1024), (256, 2048, 1024)),
            ((64, 2048, 1024), (64, 2048, 1024)),
        ]
    )
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/moe_gemm_bench.jsonl", "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    say(device=str(jax.devices()[0].device_kind), L=L, E=E, K=K, D=D, F=F, dtype=str(jnp.dtype(dtype)))
    for T in a.rows:
        x = jax.random.normal(keys[4], (T, D), dtype)
        args = (x, routers, wg, wu, wd)
        ref = None
        for form in a.forms:
            variants = [(None, lax.ragged_dot)]
            if form == "gmm":
                variants = [(t, _gmm_dot(*t, interpret=not on_tpu)) for t in tilings]
            for tiling, dot in variants:
                try:
                    ms, temp, out = measure(build(form, L, E, K, dot), args, a.iters)
                except Exception as e:  # a tiling the compiler refuses is a reading too
                    say(rows=T, form=form, tiling=tiling, error=f"{type(e).__name__}: {str(e)[:300]}")
                    continue
                out = jnp.asarray(out, jnp.float32)
                ref = out if ref is None else ref
                err = float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
                say(rows=T, gemm_rows=T * K, form=form, tiling=tiling, ms_per_layer=ms / L, temp_bytes=temp, max_rel_diff_vs_first=err)


if __name__ == "__main__":
    main()
