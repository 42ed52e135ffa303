"""The plain reference of the ``renamed`` family: a dense grouped-query decoder
in float32, written apart from the llama one and from the program. It shares
only what any reference shares (``benchmark/reference.py``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import F32, _f32, _rms

CONTROLS = ("fp8",)


def _rotate(x, theta):
    """x: [T, heads, head_dim]; the pair (i, i + head_dim/2) of position t turns by t * theta^(-2i/head_dim)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def forward(params, mc, seqs, positions, lower=None) -> list:
    """Float32 logits of each sequence at its ``positions``, one sequence at a time."""
    out = []
    H, KVH, hd, eps = mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        head = params["lm_head"].astype(F32) if "lm_head" in params else params["embed"].astype(F32).T
        for seq, wanted in zip(seqs, positions):
            T = len(seq)
            h = params["embed"].astype(F32)[jnp.asarray(seq)]
            for l in range(mc.num_layers):
                w = {k: _f32(v[l], lower) if v.ndim == 3 else v[l] for k, v in params["layers"].items()}
                x = _rms(h, w["attn_norm"], eps)
                q = _rotate((x @ w["wq"]).reshape(T, H, hd), mc.rope_theta)
                k = _rotate((x @ w["wk"]).reshape(T, KVH, hd), mc.rope_theta)
                v = (x @ w["wv"]).reshape(T, KVH, hd)
                k, v = jnp.repeat(k, H // KVH, axis=1), jnp.repeat(v, H // KVH, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
                h = h + jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(T, H * hd) @ w["wo"]
                x = _rms(h, w["mlp_norm"], eps)
                h = h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
            h = _rms(h, params["final_norm"], eps)
            out.append(np.asarray(h[jnp.asarray(np.asarray(wanted, np.int32))] @ head))
    return out
