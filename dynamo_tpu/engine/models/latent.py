"""The latent-attention mixers of the layer-group step programs: the kinds
"mla_full" and "mla_window" of ``ModelConfig.layer_types`` (``hybrid.py``
drives them; ``mla.py``, the older whole-stack family, calls ``attend`` and
``absorb`` here for its own rows).

Both kinds are multi-head latent attention behind low-rank queries, with the
sizes of their own (``ModelConfig.latent_sizes``)::

    c_q = n(x W_qa) * f_q;  q = c_q W_qb -> [H, nope + rope]; the rope lanes rotated
    [c_kv ; k_r] = x W_kva;  c_kv = n(c_kv) * f_kv;  k_r rotated, one for all heads
    score[h, t, s] = (q_n W_uk[h] . c_kv[s] + q_r . k_r[s]) / sqrt(nope + rope)     (absorbed: nothing per key)
    o[h] = (sum_s p c_kv[s]) W_uv[h];   out = concat_h(sigmoid(x W_g)[h] o[h]) W_o

``f`` is ``sqrt(hidden_size / rank)`` where ``mla_lora_rescale``. The cached
row is ``[c_kv ; k_r]``. The rope pairs lanes (2i, 2i + 1); a rotated vector is
kept with its even lanes first and its odd lanes after them (``rope_pairs``):
queries and keys are permuted alike, so no dot product changes and nothing is
interleaved again.

A full layer keeps one pool row a token and a second one, the indexer's key
(``SlotKv``: the ``v`` side's pool). Its learned indexer scores every cached
row for a query in float32, ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``
and the query attends the ``index_topk`` rows of largest score among ``s <=
t`` (ties to the lower position), all of them while there are no more. A step
writes its rows into the pool first and then attends through the block table,
so a chunk's own rows and a decode row's own are cached rows like any other.
Length-1 rows take the exact ``lax.top_k`` of their scores and gather the
chosen rows through the table; a chunk's queries take the threshold form of
the same choice (``topk_mask``: the k-th largest score by bisection on the
floats' bits, ties cut by position) as a mask over the prefix, which they
attend a page at a time under a running softmax: a chunk's work follows its
prefix, whatever the table's width. A table that holds no more than
``index_topk`` rows needs no choice: the keys are written, the scores skipped.

A window layer attends positions ``t - sliding_window < s <= t`` and holds no
blocks: position ``t``'s row lies in row ``t mod sliding_window`` of the ring
in its sequence's slot (``SlotKv``: the ``v`` side's slots). A length-1 row
writes its row and attends the ring; a chunk attends ``[the ring as it found
it ; its own rows]`` under the window mask and then writes its last
``sliding_window`` valid rows. Rows are told by position alone, so a slot
needs no zeroing to be taken.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import LATENT_KINDS, LatentSizes, ModelConfig  # noqa: F401 - hybrid reads the kinds off this module
from dynamo_tpu.engine.models import llama
from dynamo_tpu.engine.models.llama import apply_rope, rms_norm

def init_mixer(c: ModelConfig, kind: str, n: int, key: jax.Array, dtype) -> Dict[str, jax.Array]:
    """Random weights (testing) of ``n`` layers of ``kind``, stacked."""
    z, D = c.latent_sizes(kind), c.hidden_size
    ks = iter(jax.random.split(key, 16))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return (jax.random.normal(next(ks), (n, *shape), dtype=jnp.float32) * scale).astype(dtype)

    def gain(width):
        return (1.0 + 0.1 * jax.random.normal(next(ks), (n, width), dtype=jnp.float32)).astype(dtype)

    out = {
        "attn_norm": gain(D), "w_qa": dense((D, z.q_rank)), "q_norm": gain(z.q_rank),
        "w_qb": dense((z.q_rank, z.heads * (z.nope + z.rope))),
        "w_kva": dense((D, z.row)), "kv_norm": gain(z.kv_rank),
        "w_uk": dense((z.heads, z.nope, z.kv_rank), z.kv_rank ** -0.5), "w_uv": dense((z.heads, z.kv_rank, z.value)),
        "wo": dense((z.heads * z.value, D)),
    }
    if c.attention_gate:
        out["w_g"] = dense((D, z.heads))
    if kind == "mla_full":
        Hi, di = c.index_n_heads, c.index_head_dim
        out.update(wi_q=dense((z.q_rank, Hi * di)), wi_k=dense((D, di)), wi_k_gain=gain(di),
                   wi_k_bias=dense((1, di), 0.1)[:, 0], wi_w=dense((D, Hi)))
    return out


def rope_pairs(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate the pairs (2i, 2i + 1) of ``x [R, heads, d]``; the result holds
    the pairs' first lanes, then their second ones."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def wide(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """``einsum(eq, a, b)`` with a float32 result of compute-dtype operands: on a
    TPU the product leaves the MXU wide; elsewhere (XLA:CPU has no bf16 x bf16
    = f32 dot) the operands are widened first."""
    if llama._on_tpu():
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))


def _latent(c: ModelConfig, x: jax.Array, gain: jax.Array, rank: int) -> jax.Array:
    """A low-rank projection's norm, rescaled where the configuration says so."""
    y = rms_norm(x, gain, c.rms_norm_eps, out_dtype=jnp.float32)
    return (y * (c.hidden_size / rank) ** 0.5 if c.mla_lora_rescale else y).astype(x.dtype)


def absorb(q_nope: jax.Array, w_uk: jax.Array) -> jax.Array:
    """Queries ``[R, H, nope]`` through ``W_uk [H, nope, rank]``: they meet the latent as it is cached."""
    return jnp.einsum("rhn,hnk->rhk", q_nope, w_uk)


def project(c: ModelConfig, z: LatentSizes, lp, x: jax.Array, positions: jax.Array):
    """The rows of ``x [R, D]`` (normed): absorbed queries ``[R, H, row]``
    (latent lanes, then the rotated ones), the rows to cache ``[R, row]`` and
    the queries' latent ``c_q [R, q_rank]`` (the indexer reads it too)."""
    R = x.shape[0]
    with jax.named_scope("latent_proj"):
        c_q = _latent(c, x @ lp["w_qa"], lp["q_norm"], z.q_rank)
        q = (c_q @ lp["w_qb"]).reshape(R, z.heads, z.nope + z.rope)
        q = jnp.concatenate([absorb(q[..., :z.nope], lp["w_uk"]), rope_pairs(q[..., z.nope:], positions, z.theta)], axis=-1)
        kv = x @ lp["w_kva"]
        k_r = rope_pairs(kv[:, None, z.kv_rank:], positions, z.theta)[:, 0]
        return q, jnp.concatenate([_latent(c, kv[:, :z.kv_rank], lp["kv_norm"], z.kv_rank), k_r], axis=-1), c_q


def to_lanes(a: jax.Array, lanes: int) -> jax.Array:
    """``a`` with zeros after its last axis up to ``lanes`` (the pool's rows are whole tiles wide: no product changes)."""
    return a if a.shape[-1] == lanes else jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, lanes - a.shape[-1])])


def attend(q: jax.Array, rows: jax.Array, mask: jax.Array, rank: int, scale: float) -> jax.Array:
    """Absorbed attention: queries ``[R, H, row]`` over cached rows ``[S,
    row]`` (one set for all queries) or ``[R, S, row]`` (a set a query) under
    ``mask [R, S]``; the weighted latents ``[R, H, rank]``. A query whose mask
    is empty gets a mean of whatever stands there: its row is nobody's."""
    one = rows.ndim == 2
    with jax.named_scope("latent_attend"):
        s = wide("rhc,sc->rhs" if one else "rhc,rsc->rhs", to_lanes(q, rows.shape[-1]), rows) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1).astype(q.dtype)
        return jnp.einsum("rhs,sk->rhk" if one else "rhs,rsk->rhk", p, rows[..., :rank])


def output(c: ModelConfig, z: LatentSizes, lp, x: jax.Array, lat: jax.Array) -> jax.Array:
    """Weighted latents ``[R, H, rank]`` through ``W_uv``, the gate a head and ``W_o``."""
    with jax.named_scope("latent_out"):
        o = jnp.einsum("rhk,hkv->rhv", lat, lp["w_uv"])
        if c.attention_gate:
            o = (o.astype(jnp.float32) * jax.nn.sigmoid((x @ lp["w_g"]).astype(jnp.float32))[..., None]).astype(lat.dtype)
        return o.reshape(x.shape[0], z.heads * z.value) @ lp["wo"]


# --- the indexer -------------------------------------------------------------


def _rope_head(x: jax.Array, positions: jax.Array, theta: float, lanes: int) -> jax.Array:
    """Rotate the first ``lanes`` lanes of ``x [R, heads, d]`` (halves paired)."""
    return jnp.concatenate([apply_rope(x[..., :lanes], positions, theta), x[..., lanes:]], axis=-1)


def index_key(c: ModelConfig, lp, x: jax.Array, positions: jax.Array) -> jax.Array:
    """The indexer's key of each row, ``[R, index_head_dim]``: a LayerNorm with bias, its first rope lanes rotated."""
    with jax.named_scope("index_proj"):
        k = (x @ lp["wi_k"]).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + c.rms_norm_eps)
        k = (k * lp["wi_k_gain"].astype(jnp.float32) + lp["wi_k_bias"].astype(jnp.float32)).astype(x.dtype)
        return _rope_head(k[:, None], positions, float(c.rope_theta), c.qk_rope_head_dim)[:, 0]


def index_query(c: ModelConfig, lp, c_q: jax.Array, x: jax.Array, positions: jax.Array):
    """The indexer's queries ``[R, Hi, di]`` and head weights ``[R, Hi]`` (float32)."""
    Hi, di = c.index_n_heads, c.index_head_dim
    with jax.named_scope("index_proj"):
        q = _rope_head((c_q @ lp["wi_q"]).reshape(-1, Hi, di), positions, float(c.rope_theta), c.qk_rope_head_dim)
        return q, (x @ lp["wi_w"]).astype(jnp.float32) * (Hi ** -0.5 * di ** -0.5)


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """``I[r, s] = sum_j w[r, j] relu(q[r, j] . keys[s])``, float32; ``keys`` ``[S, di]`` or a set a query ``[R, S, di]``."""
    with jax.named_scope("index_scores"):
        dots = wide("rhd,sd->rhs" if keys.ndim == 2 else "rhd,rsd->rhs", q, keys)
        s = jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)
        return jnp.where(s == 0.0, 0.0, s)  # one zero: a sum of -0.0s would sort below the +0.0s it equals


def _ordered_bits(x: jax.Array) -> jax.Array:
    """Float32 -> uint32 whose order is the floats' (no NaNs)."""
    u = lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def topk_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """The exact top-``k`` of ``scores [R, S]`` among ``valid``, as a mask:
    the k-th largest score by bisection on the bits (32 counts a row), every
    score above it, and of those equal to it the lowest positions up to
    ``k``. Fewer than ``k`` valid: all of them."""
    with jax.named_scope("index_topk"):
        bits = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))

        def step(i, thr):
            cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
            enough = jnp.sum(bits >= cand[:, None], axis=-1) >= k
            return jnp.where(enough, cand, thr)

        thr = lax.fori_loop(0, 32, step, jnp.zeros((scores.shape[0],), jnp.uint32))[:, None]
        above, level = bits > thr, bits == thr
        room = k - jnp.sum(above, axis=-1, keepdims=True)
        return valid & (above | (level & (jnp.cumsum(level, axis=-1) <= room)))


def topk_rows(scores: jax.Array, valid: jax.Array, k: int):
    """The same choice as indices: ``(idx [R, k], chosen [R, k])``, ``chosen``
    False where fewer than ``k`` rows were valid."""
    with jax.named_scope("index_topk"):
        top, idx = lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
        return idx, top > -jnp.inf


# --- a full layer's attention over the pool -----------------------------------


def full_rows(c: ModelConfig, z: LatentSizes, lp, q, c_q, x, positions, tables_l, active, k_flat, i_flat):
    """``B`` length-1 rows of a full layer over ``tables_l [B, W]`` (the
    layer's own block ids: ``k_flat``/``i_flat`` are the pools ``[L*N, BS,
    lanes]``), their own rows written already. Returns ``(latents [B, H,
    rank], rows chosen, rows scored)``."""
    B, W = tables_l.shape
    bs, S = k_flat.shape[1], W * k_flat.shape[1]
    valid = (jnp.arange(S, dtype=jnp.int32)[None, :] <= positions[:, None]) & active[:, None]
    if S <= c.index_topk:
        with jax.named_scope("index_gather"):
            rows = k_flat[tables_l].reshape(B, S, k_flat.shape[-1])
        return attend(q, rows, valid, z.kv_rank, z.scale), jnp.sum(valid), jnp.sum(valid)
    with jax.named_scope("index_gather"):
        keys = i_flat[tables_l].reshape(B, S, i_flat.shape[-1])
    qi, w = index_query(c, lp, c_q, x, positions)
    idx, chosen = topk_rows(index_scores(qi, w, keys), valid, c.index_topk)
    with jax.named_scope("index_gather"):
        # (The chosen rows' blocks by a compare over the table's few slots: a gather of 2,048 scalars a row is slower.)
        at = (idx // bs)[:, :, None] == jnp.arange(W, dtype=jnp.int32)
        ids = jnp.sum(jnp.where(at, tables_l[:, None, :], 0), axis=-1) * bs + idx % bs
        rows = k_flat.reshape(-1, k_flat.shape[-1])[ids]  # [B, k, lanes]
    return attend(q, rows, chosen, z.kv_rank, z.scale), jnp.sum(chosen), jnp.sum(valid)


def full_chunk(c: ModelConfig, z: LatentSizes, lp, q, c_q, x, positions, table_l, k_flat, i_flat):
    """A chunk of ``T`` queries of a full layer over ``table_l [W]``, its own
    rows written already: every query chooses from its own causal prefix. The
    work follows the prefix, not the table: both passes walk the table's pages
    up to the one that holds the chunk's last row (a loop with a traced
    bound), the first scoring every page's index keys, the second attending a
    page at a time under the chosen rows' mask with a running softmax. Returns
    the latents ``[T, H, rank]``."""
    T, (W,), bs = q.shape[0], table_l.shape, k_flat.shape[1]
    S, scale = W * bs, z.scale
    pages = jnp.minimum(positions[T - 1] // bs + 1, W)
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] <= positions[:, None]
    if S > c.index_topk:
        qi, w = index_query(c, lp, c_q, x, positions)

        def score(b, scores):
            with jax.named_scope("index_gather"):
                keys = lax.dynamic_index_in_dim(i_flat, table_l[b], keepdims=False)
            return lax.dynamic_update_slice_in_dim(scores, index_scores(qi, w, keys), b * bs, axis=1)

        mask = topk_mask(lax.fori_loop(0, pages, score, jnp.zeros((T, S), jnp.float32)), mask, c.index_topk)

    q = to_lanes(q, k_flat.shape[-1])

    def attend_page(b, carry):
        m, l, acc = carry
        with jax.named_scope("index_gather"):
            rows = lax.dynamic_index_in_dim(k_flat, table_l[b], keepdims=False)  # [bs, lanes]
        with jax.named_scope("latent_attend"):
            ok = lax.dynamic_slice_in_dim(mask, b * bs, bs, axis=1)[:, None, :]
            s = jnp.where(ok, wide("rhc,sc->rhs", q, rows) * scale, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)  # (a page with no chosen row leaves m where it was)
            fade = jnp.exp(m - m_new)
            acc = acc * fade[..., None] + wide("rhs,sk->rhk", p.astype(q.dtype), rows[:, :z.kv_rank])
            return m_new, l * fade + jnp.sum(p, axis=-1), acc

    m0 = jnp.full((T, z.heads), -1e30, jnp.float32)
    _, l, acc = lax.fori_loop(0, pages, attend_page, (m0, jnp.zeros_like(m0), jnp.zeros((T, z.heads, z.kv_rank), jnp.float32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(c_q.dtype)


# --- a window layer's attention over the rings --------------------------------


def window_rows(z: LatentSizes, q, row, positions, slots_l, active, rings):
    """``B`` length-1 rows of a window layer: each writes its row into its
    ring (``rings [L_w*S, window, row]``, ``slots_l`` the layer's own slot
    rows) and attends the ring. Returns ``(latents, rings)``."""
    Wn = rings.shape[1]
    rings = rings.at[slots_l, positions % Wn].set(row.astype(rings.dtype))
    mask = (jnp.arange(Wn, dtype=jnp.int32)[None, :] <= positions[:, None]) & active[:, None]
    return attend(q, rings[slots_l].astype(q.dtype), mask, z.kv_rank, z.scale), rings


def window_chunk(z: LatentSizes, q, row, positions, slot_l, valid_len, rings):
    """A chunk of ``T`` queries of a window layer (``positions`` its rows',
    the first ``valid_len`` of them real): over ``[the ring as it lies ; the
    chunk's rows]`` under the window mask; the ring then takes the chunk's
    last valid rows. Returns ``(latents, rings)``."""
    T, Wn = q.shape[0], rings.shape[1]
    ring = lax.dynamic_index_in_dim(rings, slot_l, keepdims=False)
    first = positions[0]
    ring_pos = first - 1 - (first - 1 - jnp.arange(Wn, dtype=jnp.int32)) % Wn  # the last position before the chunk in each row
    real = jnp.arange(T, dtype=jnp.int32) < valid_len
    key_pos = jnp.concatenate([ring_pos, positions])
    ahead = positions[:, None] - key_pos[None, :]
    mask = jnp.concatenate([ring_pos >= 0, real])[None, :] & (ahead >= 0) & (ahead < Wn)
    lat = attend(q, jnp.concatenate([ring.astype(q.dtype), row]), mask, z.kv_rank, z.scale)
    keep = real & (jnp.arange(T, dtype=jnp.int32) >= valid_len - Wn)
    ring = ring.at[jnp.where(keep, positions % Wn, Wn)].set(row.astype(ring.dtype), mode="drop")
    return lat, lax.dynamic_update_index_in_dim(rings, ring, slot_l, axis=0)
