"""The plain reference of the ``dots3`` family: the forward pass of a
dots3-note-prev language model (latent attention with a learned indexer in the
full layers, latent attention of its own sizes under a sliding window in the
others, a sigmoid gate a head on both; a dense first layer, then sigmoid
routing over the experts beside one shared expert) in ``jax.numpy`` and
float32 at ``highest`` matmul precision.

No cache, no pool, no rings, no blocks, no kernels, no absorbed products:
whole sequences, one at a time, the attention a block of queries at a time so
that its scores fit. With ``x`` the residual stream, ``n`` RMSNorm with a
learned gain, sizes ``(H, d_n, d_r, d_v, r_q, r_kv, theta)`` of the layer's
kind and ``f_r = sqrt(hidden / r)``::

    x = embed[ids]
    per layer, attention on h = n(x):
        c_q = n(h W_qa) f_rq;  q = c_q W_qb -> [H, d_n + d_r] = [q_n ; q_r];  q_r = rope(q_r, t)     pairs (2i, 2i+1)
        [c_kv ; k_r] = h W_kva;  c_kv = n(c_kv) f_rkv;  k_r = rope(k_r, t)                        one k_r for all heads
        k_n[h] = c_kv W_uk[h]^T;  v[h] = c_kv W_uv[h]                                             W_kvb, its two halves
        p = softmax_{s in S_t}((q_n . k_n + q_r . k_r) / sqrt(d_n + d_r));  o[h] = sum_s p v[h]
        a = concat_h(sigmoid(h W_g)[h] o[h]) W_o;  x = x + a
      sliding layer:  S_t = {s : 0 <= t - s <= window - 1}
      full layer:     q_I = c_q W_Iq -> [H_I, d_I];  k_I = LayerNorm(h W_Ik) (gain and bias);  rope (halves paired) on the
                      first d_r lanes of both;  w = h W_Iw / sqrt(H_I d_I)
                      I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]);  S_t = the index_topk positions s <= t of largest
                      I[t, s] (ties to the lower position), all of them while t + 1 <= index_topk
    FFN on u = n(x):
        layer < first_k_dense:  x = x + W_down(silu(W_gate u) * W_up u)
        else:  s = sigmoid(u W_r);  chosen = top-k of s + b;  g = s[chosen] / sum s[chosen] * routed_scaling_factor
               x = x + sum_{e chosen and held} g_e E_e(u) + Shared(u)
    logits = n(x) W_head

ASSUMED (the published ``config.json`` pins sizes, not equations; each is
marked where it is computed): the two ``f_r`` factors are what
``apply_mla_qkv_lora_rescale`` means; the gate reads the normed stream and
multiplies before ``W_o``; rope pairs lanes (2i, 2i + 1) in the latent
attention and halves in the indexer; ``sliding_window_size`` counts the token
itself; the indexer is the published one of DeepSeek-V3.2-Exp, which this
config's ``index_*`` keys name, without its Hadamard rotation (it serves an fp8
cache and leaves every dot product unchanged: a departure in name only), its
LayerNorm at the model's epsilon; one routing group; the correction bias moves
the choice and not the weight.

An expert layer that holds a share computes the held experts only: an
assignment to any other adds nothing here, as in the program (the chip that
holds it adds it).

``lower`` names a control (``CONTROLS``): ``fp8_act`` re-rounds every matmul's
input; ``attend_all`` leaves the indexer out (every full layer attends its
whole prefix) and ``no_window`` the window (every sliding layer attends its
whole prefix): what a program whose mechanism is not at work computes;
``topk_less_1`` and ``window_less_1`` choose one row fewer. Nothing here calls
the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import ACT_CONTROLS, F32, _act, _rms

CONTROLS = ("fp8_act", "attend_all", "no_window", "topk_less_1", "window_less_1")
QUERY_BLOCK = 256  # queries whose scores are held at once


def _rope_pairs(x, theta: float):
    """Rotate the pairs (2i, 2i + 1) of ``x [T, heads, d]`` in place."""  # ASSUMED: interleaved pairs in the latent attention
    T, d = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _rope_halves(x, theta: float, lanes: int):
    """Rotate the first ``lanes`` lanes of ``x [T, heads, d]``, halves paired."""  # ASSUMED: as the published indexer
    T = x.shape[0]
    freqs = 1.0 / (theta ** (jnp.arange(0, lanes, 2, dtype=F32) / lanes))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : lanes // 2], x[..., lanes // 2: lanes]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., lanes:]], axis=-1)


def _first_k(key, k: int):
    """A mask of the ``k`` smallest of ``key [T, S]`` a row, ties to the lower index (a stable sort)."""
    key = jnp.where(key == 0.0, 0.0, key)  # one zero
    order = jnp.argsort(key, axis=-1, stable=True)[:, :k]
    return jnp.zeros(key.shape, bool).at[jnp.arange(key.shape[0])[:, None], order].set(True)


@functools.partial(jax.jit, static_argnames=("z", "eps", "rescale", "gate", "act", "window", "topk", "index", "hidden"))
def _attention(x, p, *, z, eps, rescale, gate, act, window, topk, index, hidden):
    """One attention sublayer over a whole sequence ``x [T, D]``. ``z`` the
    kind's sizes; ``window`` > 0: a sliding layer; ``topk`` > 0: the indexer
    chooses (``index = (H_I, d_I, theta)``). Returns ``(a [T, D], chosen [T, T]
    bool or None)``."""
    H, dn, dr, dv, rq, rkv, theta = z
    T = x.shape[0]
    h = _rms(x, p["attn_norm"], eps)
    fq, fkv = ((hidden / rq) ** 0.5, (hidden / rkv) ** 0.5) if rescale else (1.0, 1.0)  # ASSUMED: apply_mla_qkv_lora_rescale
    c_q = _rms(_act(h, act) @ p["w_qa"], p["q_norm"], eps) * fq
    q = (_act(c_q, act) @ p["w_qb"]).reshape(T, H, dn + dr)
    q_n, q_r = q[..., :dn], _rope_pairs(q[..., dn:], theta)
    kv = _act(h, act) @ p["w_kva"]
    c_kv = _rms(kv[:, :rkv], p["kv_norm"], eps) * fkv
    k_r = _rope_pairs(kv[:, None, rkv:], theta)[:, 0]
    k_n = jnp.einsum("sk,hnk->shn", _act(c_kv, act), p["w_uk"])
    v = jnp.einsum("sk,hkv->shv", _act(c_kv, act), p["w_uv"])
    at, pad = jnp.arange(T), -T % QUERY_BLOCK
    blocks = lambda a: jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]).reshape(-1, QUERY_BLOCK, *a.shape[1:])  # noqa: E731 - queries by blocks, zeros after the last
    allowed = at[None, :] <= at[:, None]
    if window:
        allowed = allowed & (at[:, None] - at[None, :] < window)  # ASSUMED: the window counts the token itself
    if topk and T > topk:
        Hi, di, theta_i = index
        q_i = _rope_halves((_act(c_q, act) @ p["wi_q"]).reshape(T, Hi, di), theta_i, dr)
        k_i = _act(h, act) @ p["wi_k"]
        k_i = k_i - jnp.mean(k_i, axis=-1, keepdims=True)
        # ASSUMED: the published indexer's LayerNorm on its key, gain and bias, at the model's epsilon
        k_i = k_i * jax.lax.rsqrt(jnp.mean(k_i * k_i, axis=-1, keepdims=True) + eps) * p["wi_k_gain"] + p["wi_k_bias"]
        k_i = _rope_halves(k_i[:, None], theta_i, dr)[:, 0]
        w = (_act(h, act) @ p["wi_w"]) * (Hi ** -0.5 * di ** -0.5)

        def choose(a):
            q_b, w_b, ok = a
            scores = jnp.sum(jax.nn.relu(jnp.einsum("thd,sd->ths", q_b, k_i)) * w_b[:, :, None], axis=1)
            return _first_k(jnp.where(ok, -scores, jnp.inf), topk) & ok

        allowed = jax.lax.map(choose, (blocks(q_i), blocks(w), blocks(allowed))).reshape(-1, T)[:T]

    def attend(a):
        qn_b, qr_b, ok = a
        s = (jnp.einsum("thn,shn->hts", qn_b, k_n) + jnp.einsum("thr,sr->hts", qr_b, k_r)) * (dn + dr) ** -0.5
        pr = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shv->thv", jnp.where(ok[None], pr, 0.0), v)

    ok = jnp.concatenate([allowed, jnp.broadcast_to(at[None, :] == 0, (pad, T))])  # a padded query sees position 0: no empty softmax
    o = jax.lax.map(attend, (blocks(q_n), blocks(q_r), ok.reshape(-1, QUERY_BLOCK, T))).reshape(-1, H, dv)[:T]
    if gate:
        o = o * jax.nn.sigmoid(_act(h, act) @ p["w_g"])[..., None]  # ASSUMED: headwise, on the normed stream, before W_o
    return _act(o.reshape(T, H * dv), act) @ p["wo"], allowed


@functools.partial(jax.jit, static_argnames=("act",))
def _swiglu(x, wg, wu, wd, *, act):
    x = _act(x, act)
    return _act(jax.nn.silu(x @ wg) * (x @ wu), act) @ wd


@functools.partial(jax.jit, static_argnames=("k", "norm", "scaling"))
def _route(u, router, bias, *, k, norm, scaling):
    """Scores, the chosen experts and their weights: ``(ids [T, k], weights [T, k])``."""
    s = jax.nn.sigmoid(u @ router)
    key = -(s + bias)  # ASSUMED: one group; the correction bias moves the choice, not the weight
    ids = jnp.argsort(jnp.where(key == 0.0, 0.0, key), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, ids, axis=-1)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * scaling


def expert_layer(u, p, mc, act=None, first: int | None = None, held: int | None = None, shared: bool = True):
    """The expert FFN on its normed input ``u [T, D]`` (float32 weights ``p``
    of ONE layer, the expert stacks holding experts ``[first, first + held)``):
    the held experts' gated sum and, with ``shared``, the shared expert."""
    first = mc.first_expert_held if first is None else first
    held = mc.experts_held if held is None else held
    ids, w = _route(u, p["router"].astype(F32), p["router_bias"].astype(F32), k=mc.num_experts_per_tok,
                    norm=mc.norm_topk_prob, scaling=float(mc.routed_scaling_factor))
    out = jnp.zeros_like(u)
    for e in np.unique(np.asarray(ids)):
        if not first <= e < first + held:
            continue  # another chip's expert: it adds nothing here
        gate = jnp.sum(jnp.where(ids == int(e), w, 0.0), axis=-1)
        out = out + gate[:, None] * _swiglu(u, *(p[n][int(e) - first].astype(F32) for n in ("w_gate", "w_up", "w_down")), act=act)
    if shared and mc.shared_intermediate_size:
        out = out + _swiglu(u, *(p[n].astype(F32) for n in ("shared_gate", "shared_up", "shared_down")), act=act)
    return out


def forward(params, mc, seqs, positions, lower: str | None = None, chosen: list | None = None) -> list:
    """Float32 logits (on the host) of each sequence of ``seqs`` at its
    ``positions``: a list of ``[len(positions[i]), V]`` arrays. ``chosen``, a
    list, takes for each sequence the full layers' attended sets ``[L_full, T,
    T]`` bool."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"no control {lower!r} (have {CONTROLS})")
    act = lower if lower in ACT_CONTROLS else None
    eps = float(mc.rms_norm_eps)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)  # noqa: E731
    layer = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    small = lambda tree: {k: v for k, v in tree.items() if k not in ("w_gate", "w_up", "w_down")}  # noqa: E731

    def run(seq):
        x = embed[jnp.asarray(np.asarray(seq, np.int32))]
        at = {"mla_full": 0, "mla_window": 0}
        sets = []
        for l, kind in enumerate(mc.layer_types):
            full = kind == "mla_full"
            topk = (mc.index_topk - (lower == "topk_less_1")) if full and lower != "attend_all" else 0
            window = 0 if full or lower == "no_window" else mc.sliding_window - (lower == "window_less_1")
            a, allowed = _attention(
                x, f32(layer(params[kind], at[kind])), z=tuple(mc.latent_sizes(kind)), eps=eps, rescale=mc.mla_lora_rescale,
                gate=mc.attention_gate, act=act, window=window, topk=topk,
                index=(mc.index_n_heads, mc.index_head_dim, float(mc.rope_theta)), hidden=mc.hidden_size)
            if full:
                sets.append(allowed)
            at[kind] += 1
            x = x + a
            if l < mc.first_k_dense:
                p = f32(layer(params["dense"], l))
                x = x + _swiglu(_rms(x, p["mlp_norm"], eps), p["w_gate"], p["w_up"], p["w_down"], act=act)
            else:
                le = l - mc.first_k_dense
                p = f32(layer(small(params["layers"]), le))
                p.update({n: params["layers"][n][le] for n in ("w_gate", "w_up", "w_down")})  # an expert at a time
                x = x + expert_layer(_rms(x, p["mlp_norm"], eps), p, mc, act)
        return x, sets

    out = []
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        head = params["lm_head"].astype(F32) if "lm_head" in params else embed.T
        for seq, wanted in zip(seqs, positions):
            x, sets = run(seq)
            if chosen is not None:
                chosen.append(np.asarray(jnp.stack(sets)))
            h = _rms(x[jnp.asarray(np.asarray(wanted, np.int32))], params["final_norm"].astype(F32), eps)
            out.append(np.asarray(h @ head))
    return out
