"""The plain reference of the ``llama`` family: the forward pass of a
Llama/Mistral/Mixtral decoder in ``jax.numpy`` and float32 at ``highest``
matmul precision.

No cache, no chunks, no kernels: dense causal attention over whole sequences
(padded to one length; a causal mask keeps the padding out of every real
position), grouped-query heads repeated, rotary embedding on half-split pairs
(the Hugging Face Llama/Mistral convention), RMS norm, SwiGLU, and for sparse
experts top-k routing with a softmax over the chosen k (Mixtral). What any
reference shares (float32 weights, the controls' re-rounding, RMS norm) is
``benchmark/reference.py``. Nothing here calls the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import ACT_CONTROLS, F32, WEIGHT_CONTROLS, _act, _f32, _rms

CONTROLS = WEIGHT_CONTROLS + ACT_CONTROLS


def _rope(x, theta):
    """x: [n, T, heads, head_dim]; position t rotates pair (i, i + hd/2)."""
    T, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "theta", "eps", "act"))
def _attention(h, norm_w, wq, wk, wv, wo, *, heads, kv_heads, head_dim, theta, eps, act):
    n, T = h.shape[:2]
    x = _act(_rms(h, norm_w, eps), act)
    q = _rope((x @ wq).reshape(n, T, heads, head_dim), theta)
    k = _rope((x @ wk).reshape(n, T, kv_heads, head_dim), theta)
    v = (x @ wv).reshape(n, T, kv_heads, head_dim)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) * (head_dim ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v).reshape(n, T, heads * head_dim)
    return h + _act(o, act) @ wo


@functools.partial(jax.jit, static_argnames=("act",))
def _swiglu(x, wg, wu, wd, act=None):
    x = _act(x, act)
    return _act(jax.nn.silu(x @ wg) * (x @ wu), act) @ wd


@functools.partial(jax.jit, static_argnames=("k",))
def _route(x, router, k):
    top, idx = jax.lax.top_k(x @ router, k)
    return jax.nn.softmax(top, axis=-1), idx


def forward(params, mc, seqs, positions, lower: str | None = None) -> list:
    """Float32 logits (on the host) of each sequence of ``seqs`` at its
    ``positions``: a list of ``[len(positions[i]), V]`` arrays."""
    act = lower if lower in ACT_CONTROLS else None
    T = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    with jax.default_matmul_precision("highest"):
        L = params["layers"]
        # One layer's (one expert's) slice at a time: a whole layer of experts would be a copy of gigabytes.
        take = lambda name, l, e=None: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a[l] if e is None else a[l, e], L[name])
        h = params["embed"].astype(F32)[jnp.asarray(tokens)]
        for l in range(mc.num_layers):
            w = {k: _f32(take(k, l), lower) for k in ("wq", "wk", "wv", "wo")}
            h = _attention(h, take("attn_norm", l), w["wq"], w["wk"], w["wv"], w["wo"],
                           heads=mc.num_heads, kv_heads=mc.num_kv_heads, head_dim=mc.head_dim,
                           theta=float(mc.rope_theta), eps=float(mc.rms_norm_eps), act=act)
            del w
            x = _rms(h, take("mlp_norm", l), mc.rms_norm_eps)
            if mc.num_experts == 0:
                y = _swiglu(x, *(_f32(take(k, l), lower) for k in ("w_gate", "w_up", "w_down")), act=act)
            else:
                gate, idx = _route(x, take("router", l).astype(F32), mc.num_experts_per_tok)
                y = jnp.zeros_like(x)
                for e in range(mc.num_experts):
                    ye = _swiglu(x, *(_f32(take(k, l, e), lower) for k in ("w_gate", "w_up", "w_down")), act=act)
                    y = y + ye * jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1, keepdims=True)
            h = h + y
        h = _rms(h, params["final_norm"], mc.rms_norm_eps)
        head = params.get("lm_head")
        head = head.astype(F32) if head is not None else params["embed"].astype(F32).T
        return [np.asarray(h[i, jnp.asarray(np.asarray(p, np.int32))] @ head) for i, p in enumerate(positions)]
