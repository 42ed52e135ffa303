"""The plain reference of the ``zaya`` family: the forward pass of a ZAYA1
decoder (attention in a compressed latent behind two causal convolutions and a
value shift; top-1 of 16 experts and a skip choice behind a router MLP that
carries its state from layer to layer; a residual merge with learned vectors)
in ``jax.numpy`` and float32 at ``highest`` matmul precision.

No cache, no slots, no blocks, no kernels, no chunks: whole sequences, one at
a time; the convolutions are shifted sums and the shift a roll with a zero
first row. With ``x`` the residual stream, ``n`` RMSNorm with a learned gain,
``s`` the router's carried state (zero before layer 0)::

    x = embed[ids]
    per layer, attention sublayer on h = n(x):
        [q~ | k~ | v1 | v2] = h W_in                          (Wq, Wk, Wv1, Wv2 side by side)
        c = [q~ ; k~]                                          heads of head_dim lanes: Hq of queries, Hk of keys
        y_t = w0[0] c_{t-1} + w0[1] c_t + b0                   depthwise, two taps, zeros before t = 0
        z_t[g] = y_{t-1}[g] W1[g, :d] + y_t[g] W1[g, d:] + b1[g]   grouped by head, two taps
        q_t[h] = z_t[h] + (q~_t[h] + k~_t[h // G]) / 2         the q-k mean goes round the convolutions
        k_t[j] = z_t[Hq + j] + (mean_{h in group j} q~_t[h] + k~_t[j]) / 2
        q^ = rope(q / |q|),  k^ = rope(tau_j k / |k|)          per head; the leading rope_fraction of the lanes rotate
        v_t = [v1_t ; v2_{t-1}]                                the value shift: the second half of the heads from the token before
        a = softmax_causal(q^ k^T) v W_o                       no 1/sqrt(d): the temperature tau stands for it
        x = (gx x + bx) + (gf a + bf)
    expert sublayer on u = n(x):
        r = u W_d + b_d + gamma s;   s <- r                    the state goes on to the next layer
        p = softmax(W3 gelu(W2 gelu(W1 n(r) + b1) + b2))       over the experts and the skip choice (the last)
        e = argmax(p + beta)                                    the bias moves the choice, not the weight
        m = p[e] W_down_e(silu(W_gate_e u) * W_up_e u),  or 0 where e is the skip choice
        x = (gx x + bx) + (gf m + bf)
    logits = n(x) embed^T

ASSUMED: ``config.json`` pins the sizes and not these equations. They follow
*Compressed Convolutional Attention* (arXiv:2510.04476) and the ZAYA1 report
(arXiv:2511.17127) as ISSUE 41 states them; neither can be fetched here. Each
is marked ``# ASSUMED`` where it is computed and can be corrected there: the
grouping of the two convolutions and their biases; the q-k mean and how it
crosses unequal head counts; the value shift; the unit norm and the
temperature; the router MLP's depth, its exact (erf) GELUs and its norm; the
carried router state; the skip choice, its index and its zero output; the
balancing bias; the residual merge's four vectors.

``lower`` names a control (``CONTROLS``): ``fp8_act`` re-rounds every matmul's
input (as the other families' does); ``bf16_router`` computes the router from
``r`` on in bfloat16 (weights, activations and probabilities rounded); the
other five break the bookkeeping the way a cache manager or an expert layer
could: ``stale_slot`` (a sequence starts from the columns the sequence before
it left: a slot not zeroed, or another sequence's), ``no_conv_carry`` (the
convolutions and the shift see zeros before every multiple of a chunk, two
blocks: columns not carried between chunks), ``no_value_shift`` (the shifted
value heads read this token), ``no_router_carry`` (``s`` = 0 in every layer)
and ``skip_computed`` (the skip choice is sent to expert 0 and added). What
any reference shares is ``benchmark/reference.py``. Nothing here calls the
program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import ACT_CONTROLS, F32, _act, _f32, _rms

BOOKKEEPING_CONTROLS = ("stale_slot", "no_conv_carry", "no_value_shift", "no_router_carry", "skip_computed")
CONTROLS = BOOKKEEPING_CONTROLS + ("bf16_router", "fp8_act")


def _bf16(x):
    """Rounded to bfloat16 and back (``reduce_precision``: XLA may drop a pair of converts as excess precision)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rope(x, theta: float, rotating: int):
    """Rotate the leading ``rotating`` lanes of each head of ``x [T, heads, d]`` (halves paired, as rotate_half)."""
    T = x.shape[0]
    freqs = 1.0 / (theta ** (jnp.arange(0, rotating, 2, dtype=F32) / rotating))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rotating // 2], x[..., rotating // 2: rotating]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotating:]], axis=-1)


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-15)


@functools.partial(jax.jit, static_argnames=("Hq", "Hk", "d", "theta", "rotating", "eps", "act", "carry_every", "shift"))
def _attention(x, g, w_in, w0, b0, w1, b1, tau, wo, first, *, Hq, Hk, d, theta, rotating, eps, act, carry_every, shift):
    """The attention sublayer over one whole sequence; ``first [2C + kv/2]`` is
    what stands before position 0 (zeros; the control ``stale_slot``: another
    sequence's last columns). Returns ``(a [T, D], last columns)``."""
    T, C, G, half = x.shape[0], (Hq + Hk) * d, Hq // Hk, Hk * d // 2
    proj = _act(_rms(x, g, eps), act) @ w_in
    c, v_own, v_next = proj[:, :C], proj[:, C:C + half], proj[:, C + half:]

    def before(a, lo):
        """``a`` one position earlier: a roll with ``first``'s lanes (zeros) as row 0."""
        prev = jnp.concatenate([first[None, lo:lo + a.shape[1]], a[:-1]])
        if carry_every:  # the control: nothing from before a chunk's first position reaches it
            prev = jnp.where((jnp.arange(T) % carry_every == 0)[:, None], 0.0, prev)
        return prev

    y = w0[0] * before(c, 0) + w0[1] * c + b0  # ASSUMED: depthwise over all 1280 channels, with a bias
    heads = lambda a: a.reshape(T, Hq + Hk, d)  # noqa: E731
    z = (jnp.einsum("tgi,gio->tgo", heads(before(y, C)), w1[:, :d]) + jnp.einsum("tgi,gio->tgo", heads(y), w1[:, d:])
         + b1.reshape(Hq + Hk, d))  # ASSUMED: one group a head, each mixing its own 128 channels, with a bias
    qt, kt = heads(c)[:, :Hq], heads(c)[:, Hq:]
    # ASSUMED: the q-k mean, added after the convolutions; a query head pairs with its key head, a key head with its group's mean
    q = z[:, :Hq] + 0.5 * (qt + jnp.repeat(kt, G, axis=1))
    k = z[:, Hq:] + 0.5 * (jnp.mean(qt.reshape(T, Hk, G, d), axis=2) + kt)
    q = _rope(_unit(q), theta, rotating)  # ASSUMED: unit norm a head, then a learned temperature a key head
    k = _rope(_unit(k) * tau[None, :, None], theta, rotating)
    v = jnp.concatenate([v_own, before(v_next, 2 * C) if shift else v_next], axis=1).reshape(T, Hk, d)  # ASSUMED: the value shift
    s = jnp.einsum("qkgd,skd->kgqs", q.reshape(T, Hk, G, d), k)  # the temperature stands where 1/sqrt(d) would
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(T, Hq * d)
    return _act(o, act) @ wo, jnp.concatenate([c[-1], y[-1], v_next[-1]])


@functools.partial(jax.jit, static_argnames=("eps", "act", "low"))
def _router(x, g, wd, bd, gamma, gn, w1, b1, w2, b2, w3, beta, s, *, eps, act, low):
    """``(u, p [T, E+1], choice [T], r)`` of the expert sublayer's input."""
    rnd = _bf16 if low else (lambda a: a)
    u = _rms(x, g, eps)
    r = _act(u, act) @ wd + bd + gamma * s  # ASSUMED: the state of the layer before, scaled per lane, joins before the norm
    a = rnd(_rms(rnd(r), gn, eps))  # ASSUMED: RMSNorm with a gain on the router's input
    a = rnd(jax.nn.gelu(a @ rnd(w1) + b1, approximate=False))  # ASSUMED: two hidden layers, exact GELU
    a = rnd(jax.nn.gelu(a @ rnd(w2) + b2, approximate=False))
    p = rnd(jax.nn.softmax(a @ rnd(w3), axis=-1))  # ASSUMED: the skip choice is one more output, the last
    return u, p, jnp.argmax(p + beta, axis=-1), r  # ASSUMED: the balancing bias moves the choice, not the weight


@functools.partial(jax.jit, static_argnames=("act",))
def _swiglu(x, wg, wu, wd, *, act):
    x = _act(x, act)
    return _act(jax.nn.silu(x @ wg) * (x @ wu), act) @ wd


@jax.jit
def _merge(x, f, gx, bx, gf, bf):
    return (gx * x + bx) + (gf * f + bf)  # ASSUMED: four learned vectors a sublayer


def forward(params, mc, seqs, positions, lower: str | None = None, probs: list | None = None) -> list:
    """Float32 logits (on the host) of each sequence of ``seqs`` at its
    ``positions``: a list of ``[len(positions[i]), V]`` arrays. ``probs``, a
    list, takes each sequence's router probabilities ``[L, T, E + 1]``."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"no control {lower!r} (have {CONTROLS})")
    act = lower if lower in ACT_CONTROLS else None
    eps, E, d = float(mc.rms_norm_eps), mc.num_experts, mc.head_dim
    A, L = params["cca"], params["layers"]
    f32 = lambda a: a.astype(F32)  # noqa: E731
    width = 2 * (mc.num_heads + mc.num_kv_heads) * d + mc.num_kv_heads * d // 2

    def run(seq, first):
        """One sequence through the stack; ``first[l]`` stands before position 0
        in layer ``l``. Returns the last layer's stream, every layer's last
        columns and the router's probabilities."""
        x = embed[jnp.asarray(np.asarray(seq, np.int32))]
        s = jnp.zeros((x.shape[0], mc.router_hidden_size), F32)
        left, seen = [], []
        for l in range(mc.num_layers):
            p = {k: f32(A[k][l]) for k in A}
            a, last = _attention(
                x, p["attn_norm"], p["w_in"], p["conv0_w"], p["conv0_b"], p["conv1_w"], p["conv1_b"], p["k_temp"], p["wo"],
                first[l], Hq=mc.num_heads, Hk=mc.num_kv_heads, d=d, theta=float(mc.rope_theta),
                rotating=int(d * mc.rope_fraction), eps=eps, act=act,
                carry_every=2 * mc.block_size if lower == "no_conv_carry" else 0, shift=lower != "no_value_shift")
            left.append(last)
            x = _merge(x, a, p["res_gx"], p["res_bx"], p["res_gf"], p["res_bf"])
            del p
            r = {k: f32(L[k][l]) for k in L if k.startswith(("router_", "res_", "mlp_norm"))}
            u, pr, choice, state = _router(
                x, r["mlp_norm"], r["router_down"], r["router_down_b"], r["router_gamma"], r["router_norm"],
                r["router_w1"], r["router_b1"], r["router_w2"], r["router_b2"], r["router_w3"], r["router_beta"],
                s, eps=eps, act=act, low=lower == "bf16_router")
            s = jnp.zeros_like(state) if lower == "no_router_carry" else state
            seen.append(pr)
            m = jnp.zeros_like(x)
            chosen = np.asarray(choice)
            for e in np.unique(chosen):
                if e == E and lower != "skip_computed":
                    continue  # ASSUMED: the skip choice adds nothing; the token passes by the experts
                w = [_f32(L[k][l, int(e) % E], None) for k in ("w_gate", "w_up", "w_down")]  # (skip_computed: expert 0)
                gate = jnp.where(choice == int(e), jnp.take_along_axis(pr, choice[:, None], axis=1)[:, 0], 0.0)
                m = m + gate[:, None] * _swiglu(u, *w, act=act)
            x = _merge(x, m, r["res_gx"], r["res_bx"], r["res_gf"], r["res_bf"])
        return x, left, jnp.stack(seen)

    zeros = [jnp.zeros((width,), F32)] * mc.num_layers
    out = []
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        head = params["lm_head"].astype(F32) if "lm_head" in params else embed.T
        first = zeros
        if lower == "stale_slot":  # what the last sequence leaves is what the first one finds
            _, first, _ = run(seqs[-1], zeros)
        for seq, wanted in zip(seqs, positions):
            x, left, seen = run(seq, first)
            if lower == "stale_slot":
                first = left
            if probs is not None:
                probs.append(np.asarray(seen))
            h = _rms(x[jnp.asarray(np.asarray(wanted, np.int32))], f32(params["final_norm"]), eps)
            out.append(np.asarray(h @ head))
    return out
