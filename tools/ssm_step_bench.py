#!/usr/bin/env python3
"""Micro-benchmark of what a hybrid (``ModelConfig.layer_types``) step adds to
a layer, at Granite-4.0-H-Small's published widths (PERF.md §6, PR 32): which
form of each ships is decided by these readings, not by guessing.

(i)   the single-step state update of B decode rows on the slot array
      ``[L_m*S, 64, 128, 128]`` float32, donated and carried from call to call:
      - ``gather_scatter``: ``ssm[rows]`` -> ``hybrid._ssm_update`` -> ``.at[rows].set``
      - ``inplace_loop``:   a ``fori_loop`` over the rows, each a dynamic slice,
                            the update, a dynamic-update-slice
      - ``pallas_t<n>``:    ``hybrid.ssm_update_rows`` (the slot rows by scalar
                            prefetch, the state aliased) at blocks of n lane rows
      The state is stored ``[.., H/g, N, g*P]`` (``ModelConfig.mamba_state_shape``);
      the XLA forms turn it to ``[H, P, N]`` and back. (This PR's first kernel kept
      ``[H, P, N]`` and walked the heads: 22-37% of the roofline, PERF.md §6.)
      against the bytes a step needs (each row's state read and written once).
(ii)  a prefill chunk of 256 positions on one slot: ``hybrid._ssd_chunk`` (the
      chunked form) against the same positions one step at a time.
(iii) the held experts' three grouped GEMMs out of the ``[L*E_held, D, F]``
      stack at a decode step's rows (16/32/64 rows x top-10, about half on the
      36 held experts: 3-5 rows a group) and a mixed step's (256 + 32):
      ``lax.ragged_dot`` against megablox ``gmm`` at several tilings (``llama._held_dot`` ships
      rows of 128 with whole-K and whole-N tiles: ``(128, 4096, 768, 768, 4096)``).
      ``--common c`` adds ``c`` times one shared direction to every row, as the
      decode rows of ``granite-4.0-h-small-d10-e36.chat-many`` have, which all
      carry one token (part ``router``; PERF.md §6): at 2 a step's 48 rows
      visit half of the held experts at 14-17 rows a group, which is what
      that cell times; every line says how many groups its rows visited.
(iv)  ``--parts router``: why the cell's rows fall on the same experts. The
      configuration's own seeded weights (``benchmark/families/granite_hybrid``)
      through ``hybrid.prefill`` and ``hybrid.decode``, the router's input of every
      layer read out by a ``jax.debug.callback`` around ``hybrid._moe_held``
      (the program is not changed): the share of the rows' energy that lies
      in their mean, the spread over experts of the mean logit against the
      spread within a row, and the held experts the rows visit, for (1) 48
      positions of one prompt, (2) 32 decode rows of chats that end as the
      harness's template ends them, each on its greedy token, (3) the same
      rows on distinct tokens.

    chiprun -- python tools/ssm_step_bench.py
    JAX_PLATFORMS=cpu python tools/ssm_step_bench.py --tiny   # control flow only

Prints one JSON line per reading (microseconds a layer) and writes them to
``chiprun_out/ssm_step_bench.jsonl``.
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
import numpy as np
from jax import lax

from dynamo_tpu.engine.config import get_config
from dynamo_tpu.engine.models import hybrid

HBM = 819e9  # bytes/s, TPU v5e (benchmark/peaks.json)


def timed(fn, args, iters, donate=None):
    """Mean seconds a call, the first (compiling) call apart. ``donate`` names
    the argument that each call's first result replaces."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        if donate is not None:
            args = list(args)
            args[donate] = out[0] if isinstance(out, tuple) else out
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, out


def update_forms(c):
    """Each takes the slot array AS STORED (``ModelConfig.mamba_state_shape``)."""

    def gather_scatter(ssm, rows, x, Bh, Ch, dt, A, D):
        y, state = hybrid._ssm_update(hybrid._from_slot(c, ssm[rows]), x, Bh, Ch, dt, A, D)
        return ssm.at[rows].set(hybrid._to_slot(c, state)), y

    def inplace_loop(ssm, rows, x, Bh, Ch, dt, A, D):
        def body(i, carry):
            ssm, y = carry
            h = hybrid._from_slot(c, lax.dynamic_index_in_dim(ssm, rows[i], keepdims=False))
            h = h * jnp.exp(dt[i] * A)[:, None, None] + (dt[i][:, None] * x[i])[:, :, None] * Bh[i][:, None, :]
            y_i = jnp.sum(h * Ch[i][:, None, :], axis=-1) + D[:, None] * x[i]
            return lax.dynamic_update_index_in_dim(ssm, hybrid._to_slot(c, h), rows[i], axis=0), y.at[i].set(y_i)

        return lax.fori_loop(0, rows.shape[0], body, (ssm, jnp.zeros_like(x)))

    forms = {"gather_scatter": gather_scatter, "inplace_loop": inplace_loop}
    for tiles in (8, 16, 32):
        if tiles <= c.mamba_state_shape[0] or tiles == 8:
            forms[f"pallas_t{tiles}"] = lambda ssm, rows, x, Bh, Ch, dt, A, D, tiles=tiles: hybrid.ssm_update_rows(
                c, ssm, rows, x, Bh[:, 0], Ch[:, 0], dt, A, D, interpret=jax.devices()[0].platform != "tpu", tiles=tiles)
    return forms


def moe_layer(dot, E_held, K):
    """Route over all experts, sort, the held assignments' three grouped GEMMs
    (``llama._moe_held``'s dispatch with the product as a parameter)."""

    def fn(x, router, wg, wu, wd, layer):
        logits = (x @ router).astype(jnp.float32)
        vals, idx = lax.top_k(logits, K)
        w = jax.nn.softmax(vals, axis=-1).reshape(-1)
        local = idx.reshape(-1)
        held = local < E_held
        order = jnp.argsort(jnp.where(held, local, E_held))
        tok = order // K
        groups = wg.shape[0]
        sizes = jnp.zeros((groups,), jnp.int32).at[jnp.where(held, local + layer * E_held, groups)].add(1, mode="drop")
        xs = x[tok]
        y = dot(jax.nn.silu(dot(xs, wg, sizes)) * dot(xs, wu, sizes), wd, sizes)
        y = jnp.where(held[order][:, None], y * w[order].astype(x.dtype)[:, None], 0)
        return jnp.zeros_like(x).at[tok].add(y)

    return fn


def gmm_dot(tiling, interpret):
    """``tiling`` = (rows, k and n tiles of gate/up [D -> F], k and n tiles of down [F -> D])."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def dot(lhs, rhs, sizes):
        m = lhs.shape[0]
        tm = min(tiling[0], -(-m // 8) * 8)
        pad = -m % tm
        if pad:
            lhs = jnp.concatenate([lhs, jnp.zeros((pad, lhs.shape[1]), lhs.dtype)])
        tk, tn = tiling[1:3] if rhs.shape[1] >= rhs.shape[2] else tiling[3:5]
        tk, tn = min(tk, rhs.shape[1]), min(tn, rhs.shape[2])
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype, tiling=(tm, tk, tn), interpret=interpret)[:m]

    return dot


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--parts", nargs="*", default=["update", "chunk", "moe"])
    ap.add_argument("--common", nargs="*", type=float, default=[0.0], help="moe: weight of a direction all rows share")
    ap.add_argument("--moe-rows", nargs="*", type=int, default=None)
    ap.add_argument("--shipped-only", action="store_true", help="moe: ragged_dot against the tiling llama._held_dot ships")
    ap.add_argument("--seed", type=int, default=3200000011, help="router: the seed of the weights")
    a = ap.parse_args()
    on_tpu = jax.devices()[0].platform == "tpu"
    if a.tiny:
        H, P, N, S, Lm, T, D, F, E, E_held, K, L = 8, 16, 16, 9, 2, 32, 64, 32, 8, 4, 3, 2
        row_counts, moe_rows, dtype = (4, 8), (4, 40), jnp.float32
    else:
        H, P, N, S, Lm, T, D, F, E, E_held, K, L = 128, 64, 128, 65, 9, 256, 4096, 768, 72, 36, 10, 3
        row_counts, moe_rows, dtype = (16, 32, 64), (16, 32, 64, 288), jnp.bfloat16
    moe_rows = tuple(a.moe_rows or moe_rows)
    mc = get_config("tiny-hybrid").replace(hidden_size=H * P // 2, mamba_n_heads=H, mamba_d_head=P, mamba_d_state=N)
    os.makedirs("chiprun_out", exist_ok=True)
    log = open("chiprun_out/ssm_step_bench.jsonl", "a")

    def say(**kw):
        line = json.dumps(kw)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    say(device=str(jax.devices()[0].device_kind), H=H, P=P, N=N, slots=S, layers=Lm, chunk=T, D=D, F=F, E=E, held=E_held, K=K)
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    A, Dskip = -jnp.exp(jax.random.uniform(ks[0], (H,), minval=0.0, maxval=2.0)), jnp.ones((H,), jnp.float32)

    if "update" in a.parts:
        for B in row_counts:
            rs = np.random.default_rng(B)
            # Distinct slots of one layer, as a decode batch holds them; the rest of the bucket would be scratch.
            rows = jnp.asarray((Lm - 1) * S + 1 + rs.permutation(S - 1)[:B].astype(np.int32))
            x = jax.random.normal(ks[1], (B, H, P), jnp.float32)
            Bh = jnp.repeat(jax.random.normal(ks[2], (B, 1, N), jnp.float32), H, axis=1)
            Ch = jnp.repeat(jax.random.normal(ks[3], (B, 1, N), jnp.float32), H, axis=1)
            dt = jax.nn.softplus(jax.random.normal(ks[4], (B, H), jnp.float32) - 4.0)
            need = 2.0 * B * H * P * N * 4
            ref_y = None
            for name, fn in update_forms(mc).items():
                ssm = 0.1 * jax.random.normal(ks[5], (Lm * S, *mc.mamba_state_shape), jnp.float32)
                try:
                    y = jax.jit(fn)(ssm, rows, x, Bh, Ch, dt, A, Dskip)[1]  # one step from the same state: compared
                    sec, (ssm, _) = timed(jax.jit(fn, donate_argnums=(0,)), (ssm, rows, x, Bh, Ch, dt, A, Dskip), a.iters, donate=0)
                except Exception as e:  # a form the compiler refuses is a reading too
                    say(part="update", rows=B, form=name, error=f"{type(e).__name__}: {str(e)[:300]}")
                    continue
                ref_y = y if ref_y is None else ref_y
                say(part="update", rows=B, form=name, us_per_layer=sec * 1e6, needed_bytes=need,
                    roofline_pct=100.0 * need / HBM / sec if on_tpu else None,
                    max_diff_vs_first=float(jnp.max(jnp.abs(y - ref_y))))
                del ssm

    if "chunk" in a.parts:
        x = jax.random.normal(ks[6], (T, H, P), jnp.float32)
        Bh = jnp.repeat(jax.random.normal(ks[7], (T, 1, N), jnp.float32), H, axis=1)
        Ch = jnp.repeat(jax.random.normal(ks[8], (T, 1, N), jnp.float32), H, axis=1)
        dt = jax.nn.softplus(jax.random.normal(ks[9], (T, H), jnp.float32) - 4.0)
        state = 0.1 * jax.random.normal(ks[10], (H, P, N), jnp.float32)

        def chunked(state, x, Bh, Ch, dt):  # (takes and leaves the state as stored)
            y, state = hybrid._ssd_chunk(hybrid._to_slot(mc, state), x, Bh, Ch, dt, A, Dskip, T)
            return hybrid._from_slot(mc, state), y

        def stepwise(state, x, Bh, Ch, dt):
            def step(h, xs):
                y, h = hybrid._ssm_update(h[None], *(v[None] for v in xs), A, Dskip)
                return h[0], y[0]

            return lax.scan(step, state, (x, Bh, Ch, dt))

        ref = None
        for name, fn in (("ssd_chunk", chunked), ("stepwise", stepwise)):
            sec, (st, y) = timed(jax.jit(fn), (state, x, Bh, Ch, dt), a.iters)
            ref = (st, y) if ref is None else ref
            say(part="chunk", positions=T, form=name, us_per_layer=sec * 1e6,
                max_state_diff_vs_first=float(jnp.max(jnp.abs(st - ref[0]))), max_y_diff_vs_first=float(jnp.max(jnp.abs(y - ref[1]))))

    if "moe" in a.parts:
        wg = jax.random.normal(ks[0], (L * E_held, D, F), dtype) * D ** -0.5
        wu = jax.random.normal(ks[1], (L * E_held, D, F), dtype) * D ** -0.5
        wd = jax.random.normal(ks[2], (L * E_held, F, D), dtype) * F ** -0.5
        router = jax.random.normal(ks[3], (D, E), dtype) * 8 * D ** -0.5
        # (The first reading of this PR gave rows of 128 over 8-32 at every k tile; what is left to choose is the down product's tiles.)
        tilings = [(128, 128, 128, 128, 128)] if a.tiny else [
            (128, 1024, 768, 768, 1024), (128, 1024, 768, 768, 768), (128, 1024, 768, 768, 2048), (128, 2048, 768, 768, 1024),
            (128, 4096, 768, 768, 4096), (32, 4096, 768, 768, 1024)]
        if a.shipped_only and not a.tiny:
            tilings = [(128, 4096, 768, 768, 4096)]
        for rows, common in ((r, c) for c in a.common for r in moe_rows):
            x = (jax.random.normal(ks[4], (rows, D), jnp.float32) + common * jax.random.normal(ks[5], (D,), jnp.float32)).astype(dtype)
            top = np.asarray(lax.top_k((x @ router).astype(jnp.float32), K)[1])
            visited = len(np.unique(top[top < E_held]))
            shape = dict(common=common, groups_visited=visited, rows_per_group=float((top < E_held).sum() / max(visited, 1)))
            variants = [("ragged_dot", None, lax.ragged_dot)] + [("gmm", t, gmm_dot(t, not on_tpu)) for t in tilings]
            ref = None
            for form, tiling, dot in variants:
                try:
                    sec, out = timed(jax.jit(moe_layer(dot, E_held, K)), (x, router, wg, wu, wd, jnp.int32(1)), a.iters)
                except Exception as e:
                    say(part="moe", rows=rows, **shape, form=form, tiling=tiling, error=f"{type(e).__name__}: {str(e)[:300]}")
                    continue
                out = jnp.asarray(out, jnp.float32)
                ref = out if ref is None else ref
                say(part="moe", rows=rows, assignments=rows * K, **shape, form=form, tiling=tiling, us_per_layer=sec * 1e6,
                    max_rel_diff_vs_first=float(jnp.max(jnp.abs(out - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9)))

    if "router" in a.parts:
        from benchmark import families
        from dynamo_tpu.engine.kv_cache import KvCacheArrays

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs", "granite-4.0-h-small-d10-e36.json")) as f:
            cfg = json.load(f)
        if a.tiny:
            cfg = {**cfg, **{k: v for k, v in cfg["rehearsal"].items() if not isinstance(v, dict)},
                   "deployment_experts": {**cfg["deployment_experts"], **cfg["rehearsal"]["deployment_experts"]},
                   "engine": {**cfg["engine"], **cfg["rehearsal"]["engine"]}}
        fam = families.load(cfg["family"])
        mc = fam.model_config(cfg, "router-look")
        params = fam.make_params(mc, a.seed)
        taps, held_moe = [], hybrid._moe_held

        def tapped(x, lp, c, valid, experts, layer):
            jax.debug.callback(lambda xx, r, ll: taps.append((int(ll), np.asarray(xx, np.float32), np.asarray(r, np.float32))),
                               x, lp["router"], layer)
            return held_moe(x, lp, c, valid, experts, layer)

        hybrid._moe_held = tapped
        T, B, W = (16, 8, 4) if a.tiny else (256, 32, 16)
        rs = np.random.default_rng(a.seed)
        cache = KvCacheArrays.create(mc, 1 + B * W, dtype=params["embed"].dtype, num_slots=1 + B)
        k, v = cache.k, cache.v
        tables = 1 + np.arange(B * W, dtype=np.int32).reshape(B, W)
        prefill = jax.jit(lambda p, k, v, t, n, bt: hybrid.prefill(p, mc, k, v, t, n, jnp.int32(0), bt, has_prefix=False)[:3],
                          donate_argnums=(1, 2))
        decode = jax.jit(lambda p, k, v, t, pos, bt, act: hybrid.decode(p, mc, k, v, t, pos, bt, act)[0])

        def look(what, rows_of, **kw):
            jax.effects_barrier()
            for layer, x, r in sorted(taps, key=lambda t: t[0]):
                logits = rows_of(x) @ r
                mean = rows_of(x).mean(axis=0)
                top = np.argsort(-logits, axis=1)[:, : mc.num_experts_per_tok]
                here = top[top < mc.experts_held]
                say(part="router", what=what, layer=layer, seed=a.seed, rows=len(logits), **kw,
                    mean_energy_share=float((mean ** 2).sum() / (rows_of(x) ** 2).sum(axis=1).mean()),
                    mean_logit_std_over_experts=float(logits.mean(axis=0).std()),
                    logit_std_within_a_row_less_the_mean=float((logits - logits.mean(axis=0)).std()),
                    held_visited=len(np.unique(here)), held=mc.experts_held,
                    rows_per_visited=float(len(here) / max(len(np.unique(here)), 1)))
            taps.clear()

        # 1. 48 positions of one prompt of seeded tokens: what a chunk's rows look like to the router.
        k, v = hybrid.open_slot(k, v, jnp.int32(tables[0][0]), jnp.int32(1))
        out, k, v = prefill(params, k, v, jnp.asarray(rs.integers(4, mc.vocab_size, size=T), jnp.int32), jnp.int32(T), jnp.asarray(tables[0]))
        jax.block_until_ready(out)
        look("one prompt's positions", lambda x: x[:: max(1, T // 48)][:48])
        # 2. What the cell's decode rows are: B chats of seeded words that all end, as the harness's chat template ends every
        #    prompt, in the id of "<|assistant|>" (benchmark/tokenizer.py: 2); each one's greedy continuation.
        n, last, first = T // 4, 2, []
        for b in range(B):
            toks = np.zeros((T,), np.int32)
            toks[:n] = np.concatenate([rs.integers(4, mc.vocab_size, size=n - 1), [last]])
            k, v = hybrid.open_slot(k, v, jnp.int32(tables[b][0]), jnp.int32(1 + b))
            out, k, v = prefill(params, k, v, jnp.asarray(toks), jnp.int32(n), jnp.asarray(tables[b]))
            first.append(int(np.argmax(np.asarray(out, np.float32).reshape(-1, mc.vocab_size)[-1])))
        jax.effects_barrier()
        taps.clear()
        pos, act = jnp.full((B,), n, jnp.int32), jnp.ones((B,), bool)
        logits = decode(params, k, v, jnp.asarray(first, jnp.int32), pos, jnp.asarray(tables), act)
        second = np.argmax(np.asarray(logits, np.float32), axis=-1)
        look("decode rows on their greedy tokens", lambda x: x, greedy_repeats_the_last_prompt_token=int(sum(t == last for t in first)),
             distinct_first_tokens=len(set(first)), second_token_repeats_the_first=int((second == np.asarray(first)).sum()))
        # 3. The same slots, each row on a token of its own: what a trained model's rows would be nearer to.
        jax.block_until_ready(decode(params, k, v, jnp.asarray(rs.integers(4, mc.vocab_size, size=B), jnp.int32), pos, jnp.asarray(tables), act))
        look("decode rows on distinct tokens", lambda x: x)
        hybrid._moe_held = held_moe


if __name__ == "__main__":
    main()
