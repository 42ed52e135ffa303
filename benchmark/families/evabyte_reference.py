"""The plain reference of the ``evabyte`` family: the forward pass of an EVA
(chunked linear attention) byte-level decoder in ``jax.numpy`` and float32 at
``highest`` matmul precision.

No cache, no blocks, no kernels, no rolls: whole sequences, one at a time,
padded to whole windows (the padding lies behind every real position, and
attention is causal). Per layer, with ``W`` the window, ``C`` the chunk,
``w(t) = t // W``, heads ``a`` of width ``d``::

    x = rmsnorm(h, 1 + g_attn);  q, k, v = x Wq, x Wk, x Wv;  q, k = rope(q, k; position t)
    every chunk c (positions C c .. C c + C - 1), every head a:
        kbar[c, a] = sum_i softmax_i(mu_a . k[i, a]) k[i, a]
        vbar[c, a] = sum_i softmax_i(phi_a . k[i, a]) v[i, a]
    query t, head a:   S = { j : w(j) = w(t), j <= t }      exact keys of its own window
                       R = { c : w(C c) < w(t) }            summaries of every chunk of every earlier window
        o[t, a] = one softmax over  q.k_j / sqrt d (j in S)  and  q.kbar_c / sqrt d (c in R)
    h = h + o Wo;   x = rmsnorm(h, 1 + g_mlp);   h = h + (silu(x Wg) * (x Wu)) Wd
    logits = rmsnorm(h, 1 + g_f) W_head[:, 0:V]

Queries are taken a window at a time, in blocks of at most 1024, so that the
scores of 32 heads over a window and its summaries fit beside the weights.

Departures from the published model (``configs/evabyte-d16.json`` lists them
under ``assumed``), each because the modelling code cannot be fetched here:
``mu`` pools keys and ``phi`` values; pooling follows rope, and rope turns by
absolute position on half-split pairs; of the eight prediction heads of
``lm_head`` only the first ``V`` columns (the next byte) are multiplied.

``lower`` names a control: ``fp8_act`` re-rounds every matmul's input (as the
``llama`` family's does); the other three break the attention's bookkeeping
the way a cache manager could: ``no_summaries`` (R empty), ``uniform_pool``
(both poolings a plain mean), ``stale_window`` (the window before the query's
own stays visible key by key beside its summaries: a roll that forgot to
release). What any reference shares is ``benchmark/reference.py``. Nothing
here calls the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import ACT_CONTROLS, F32, _act, _f32, _rms

ATTENTION_CONTROLS = ("no_summaries", "uniform_pool", "stale_window")
CONTROLS = ATTENTION_CONTROLS + ("fp8_act",)


def _rope(x, theta):
    """x: [T, heads, head_dim]; position t rotates pair (i, i + hd/2)."""
    T, hd = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _pool(by, x, query, uniform: bool):
    """Each chunk of ``x`` [chunks, C, heads, hd] pooled by softmax(query . by) over its members."""
    if uniform:
        return jnp.mean(x, axis=1)
    w = jax.nn.softmax(jnp.sum(by * query[None, None], axis=-1), axis=1)
    return jnp.sum(w[..., None] * x, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "window", "chunk", "theta", "eps", "lower"))
def _attention(h, g, wq, wk, wv, wo, mu, phi, *, heads, head_dim, window, chunk, theta, eps, lower):
    T = h.shape[0]
    act = lower if lower in ACT_CONTROLS else None
    x = _act(_rms(h, 1.0 + g.astype(F32), eps), act)
    q = _rope((x @ wq).reshape(T, heads, head_dim), theta)
    k = _rope((x @ wk).reshape(T, heads, head_dim), theta)
    v = (x @ wv).reshape(T, heads, head_dim)
    chunks = lambda a: a.reshape(T // chunk, chunk, heads, head_dim)  # noqa: E731
    kbar = _pool(chunks(k), chunks(k), mu.astype(F32), lower == "uniform_pool")
    vbar = _pool(chunks(k), chunks(v), phi.astype(F32), lower == "uniform_pool")
    M, scale = window // chunk, head_dim ** -0.5
    causal = jnp.tril(jnp.ones((window, window), bool))
    block = min(window, 1024)  # queries at a time: the scores of a whole window of 2048 would be gigabytes
    out = []
    for w in range(T // window):
        own = slice(w * window, (w + 1) * window)
        keys, values, seen = [k[own]], [v[own]], 0
        if w and lower != "no_summaries":
            keys.append(kbar[: w * M]), values.append(vbar[: w * M])
            seen += w * M
        if w and lower == "stale_window":
            before = slice((w - 1) * window, w * window)
            keys.append(k[before]), values.append(v[before])
            seen += window
        keys, values = jnp.concatenate(keys), jnp.concatenate(values)
        for b in range(0, window, block):
            mask = jnp.concatenate([causal[b:b + block], jnp.ones((block, seen), bool)], axis=1)
            s = jnp.einsum("qhd,khd->hqk", q[own][b:b + block], keys) * scale
            s = jnp.where(mask[None], s, -jnp.inf)
            out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), values))
    o = jnp.concatenate(out).reshape(T, heads * head_dim)
    return h + _act(o, act) @ wo


@functools.partial(jax.jit, static_argnames=("eps", "act"))
def _mlp(h, g, wg, wu, wd, *, eps, act):
    x = _act(_rms(h, 1.0 + g.astype(F32), eps), act)
    return h + _act(jax.nn.silu(x @ wg) * (x @ wu), act) @ wd


def forward(params, mc, seqs, positions, lower: str | None = None) -> list:
    """Float32 logits (on the host) of each sequence of ``seqs`` at its
    ``positions``: a list of ``[len(positions[i]), V]`` arrays."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"no control {lower!r} (have {CONTROLS})")
    act = lower if lower in ACT_CONTROLS else None
    W, L = mc.window_size, params["layers"]
    take = lambda name, l: L[name][l]  # noqa: E731 - one layer's slice at a time
    out = []
    with jax.default_matmul_precision("highest"):
        embed, head = params["embed"].astype(F32), params["lm_head"][:, : mc.vocab_size].astype(F32)
        for seq, wanted in zip(seqs, positions):
            tokens = np.zeros((-(-len(seq) // W) * W,), np.int32)
            tokens[: len(seq)] = seq
            h = embed[jnp.asarray(tokens)]
            for l in range(mc.num_layers):
                w = {k: _f32(take(k, l), None) for k in ("wq", "wk", "wv", "wo")}  # no control re-rounds weights
                h = _attention(h, take("attn_norm", l), w["wq"], w["wk"], w["wv"], w["wo"], take("eva_mu", l),
                               take("eva_phi", l), heads=mc.num_heads, head_dim=mc.head_dim, window=W,
                               chunk=mc.chunk_size, theta=float(mc.rope_theta), eps=float(mc.rms_norm_eps), lower=lower)
                del w
                h = _mlp(h, take("mlp_norm", l), *(_f32(take(k, l), None) for k in ("w_gate", "w_up", "w_down")),
                         eps=float(mc.rms_norm_eps), act=act)
            h = _rms(h[jnp.asarray(np.asarray(wanted, np.int32))], 1.0 + params["final_norm"].astype(F32), mc.rms_norm_eps)
            out.append(np.asarray(h @ head))
    return out
