"""The plain reference of the ``granite_hybrid`` family: the forward pass of a
GraniteMoeHybrid decoder (Mamba-2 state-space mixers around attention layers,
sparse experts with a shared expert in every layer) in ``jax.numpy`` and
float32 at ``highest`` matmul precision.

No cache, no slots, no blocks, no kernels, no chunked form: whole sequences,
one at a time. With ``x`` the residual stream, ``rm`` the residual multiplier::

    x = embed[ids] * embedding_multiplier
    per layer:  x = x + rm * mixer(rmsnorm(x, g1));  u = rmsnorm(x, g2)
                x = x + rm * (experts(u) + shared(u))
    logits = (rmsnorm(x, g_f) @ embed^T) / logits_scaling

    attention mixer: q, k, v = u Wq, u Wk, u Wv (no rotary where the config
        says "nope"); softmax(q k^T * attention_multiplier) v, causal; @ Wo
    mamba mixer:     [z | xBC | dt] = u W_in
        xBC_t = silu(b + sum_k w_k * xBC_{t-K+1+k})           causal depthwise conv, zeros before t = 0
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t * x_t (x) B_t;  y_t = h_t C_t + D x_t     ONE POSITION AT A TIME
        y = rmsnorm(y * silu(z), g) over all inner lanes;  @ W_out
    experts: top-K of the router's logits over ALL its experts, gates = softmax
        over the K chosen logits; of the chosen, only those in ``held`` are
        computed and added (the rest are another chip's to add);
        shared(u) = W_down(silu(W_gate u) * (W_up u)), ungated, once.

Departures from the published ``GraniteMoeHybrid`` code, each because the
modelling code cannot be fetched here (the configuration file lists them under
``assumed``): the gate multiplies before the inner norm (``norm_before_gate``
false) and that norm is over all ``d_inner`` lanes as one group; ``dt`` is not
clamped (``time_step_limit`` (0, inf)); the expert and shared projections are
held as separate gate/up matrices, not the fused ``input_linear``.

``lower`` names a control (``CONTROLS``): ``fp8_act`` re-rounds every matmul's
input (as the ``llama`` family's does); ``bf16_state`` rounds the recurrent
state to bfloat16 after every position; the other three break the bookkeeping
the way a cache manager or an expert layer could: ``stale_state`` (a sequence
starts from the state and columns the sequence before it left behind: a slot
not zeroed), ``no_conv_carry`` (the convolution sees zeros before every
multiple of ``mamba_chunk_size``: columns not carried between chunks),
``all_experts`` (every chosen expert is added, an absent one through the held
expert ``(e - first) mod held`` that stands where its weights would: a layer
that does not know it holds a share). What any reference shares is
``benchmark/reference.py``. Nothing here calls the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import ACT_CONTROLS, F32, _act, _f32, _rms

STATE_CONTROLS = ("stale_state", "no_conv_carry", "all_experts", "bf16_state")
CONTROLS = STATE_CONTROLS + ("fp8_act",)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "scale", "eps", "act"))
def _attention(h, g, wq, wk, wv, wo, *, heads, kv_heads, head_dim, scale, eps, act):
    T = h.shape[0]
    x = _act(_rms(h, g, eps), act)
    q = (x @ wq).reshape(T, kv_heads, heads // kv_heads, head_dim)
    k = (x @ wk).reshape(T, kv_heads, head_dim)
    v = (x @ wv).reshape(T, kv_heads, head_dim)
    s = jnp.einsum("qkgd,skd->kgqs", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(T, heads * head_dim)
    return _act(o, act) @ wo


@functools.partial(jax.jit, static_argnames=("H", "P", "G", "N", "eps", "act", "carry_every", "bf16_state"))
def _mamba(h, g, w_in, conv_w, conv_b, dt_bias, A_log, D, g_inner, w_out, state0, cols0,
           *, H, P, G, N, eps, act, carry_every, bf16_state):
    """The mixer over one whole sequence from ``(state0 [H, P, N], cols0
    [K-1, C])``. Returns ``(out [T, D], state, cols)``."""
    T, di, K = h.shape[0], H * P, conv_w.shape[0]
    zxbcdt = _act(_rms(h, g, eps), act) @ w_in
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * G * N], zxbcdt[:, di + di + 2 * G * N:]
    seq = jnp.concatenate([cols0, xbc], axis=0)  # input t stands at row K-1+t
    taps = jnp.stack([seq[k:k + T] for k in range(K)])  # [K, T, C]: tap k of position t is input t-K+1+k
    if carry_every:
        # The control: no input from before the last multiple of ``carry_every`` reaches the convolution.
        t = jnp.arange(T)[None, :]
        src = t - (K - 1) + jnp.arange(K)[:, None]
        taps = jnp.where((src >= (t // carry_every) * carry_every)[:, :, None], taps, 0.0)
    act_xbc = jax.nn.silu(jnp.sum(taps * conv_w[:, None, :], axis=0) + conv_b)
    x = act_xbc[:, :di].reshape(T, H, P)
    Bh = jnp.repeat(act_xbc[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    Ch = jnp.repeat(act_xbc[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + dt_bias)  # [T, H]
    A = -jnp.exp(A_log)

    def step(state, xs):
        x_t, B_t, C_t, dt_t = xs
        state = state * jnp.exp(dt_t * A)[:, None, None] + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        if bf16_state:  # (reduce_precision: XLA may drop a float32 -> bfloat16 -> float32 pair of converts as excess precision)
            state = lax.reduce_precision(state, exponent_bits=8, mantissa_bits=7)
        return state, jnp.sum(state * C_t[:, None, :], axis=-1) + D[:, None] * x_t

    state, y = lax.scan(step, state0, (x, Bh, Ch, dt))
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = _rms(y, g_inner, eps)
    return _act(y, act) @ w_out, state, seq[T:]


@functools.partial(jax.jit, static_argnames=("K", "eps", "act"))
def _route(h, g, router, *, K, eps, act):
    x = _act(_rms(h, g, eps), act)
    vals, idx = lax.top_k(x @ router, K)
    return x, jax.nn.softmax(vals, axis=-1), idx


@functools.partial(jax.jit, static_argnames=("act",))
def _swiglu(x, wg, wu, wd, *, act):
    return _act(jax.nn.silu(x @ wg) * (x @ wu), act) @ wd


def _experts(x, gates, idx, stacks, l, first, held, all_experts: bool, act):
    """Sum over the chosen experts that are held (or, ``all_experts``, over
    every chosen one) of gate * expert(x), one expert's weights at a time."""
    out = jnp.zeros_like(x)
    idx_np = np.asarray(idx)
    for e in np.unique(idx_np):
        local = int(e) - first
        if not 0 <= local < held:
            if not all_experts:
                continue
            local %= held
        gate = jnp.sum(jnp.where(idx == int(e), gates, 0.0), axis=-1, keepdims=True)  # 0 where not chosen
        w = [_f32(stacks[k][l, local], None) for k in ("w_gate", "w_up", "w_down")]
        out = out + gate * _swiglu(x, *w, act=act)
    return out


def forward(params, mc, seqs, positions, lower: str | None = None, held=None) -> list:
    """Float32 logits (on the host) of each sequence of ``seqs`` at its
    ``positions``: a list of ``[len(positions[i]), V]`` arrays. ``held`` is
    ``(first, count)``, the experts whose weights ``params`` holds and whose
    assignments are added (default: the configuration's share)."""
    if lower is not None and lower not in CONTROLS:
        raise ValueError(f"no control {lower!r} (have {CONTROLS})")
    act = lower if lower in ACT_CONTROLS else None
    first, n_held = held if held is not None else (mc.first_expert_held, mc.experts_held)
    eps, rm = float(mc.rms_norm_eps), float(mc.residual_multiplier)
    scale = float(mc.attention_scale) or mc.head_dim ** -0.5
    H, P, G, N, Kc = mc.mamba_n_heads, mc.mamba_d_head, mc.mamba_n_groups, mc.mamba_d_state, mc.mamba_d_conv
    L, A, M = params["layers"], params["attn"], params.get("mamba")
    f32 = lambda a: a.astype(F32)  # noqa: E731

    def run(seq, carry):
        """One sequence through the stack from ``carry`` (a mamba layer's
        ``(state, cols)`` each): the last layer's stream and the new carry."""
        h = embed[jnp.asarray(np.asarray(seq, np.int32))] * float(mc.embedding_multiplier)
        la = lm = 0
        left = []
        for l, kind in enumerate(mc.layer_types):
            if kind == "attention":
                w = [_f32(A[k][la], None) for k in ("wq", "wk", "wv", "wo")]
                out = _attention(h, f32(A["attn_norm"][la]), *w, heads=mc.num_heads, kv_heads=mc.num_kv_heads,
                                 head_dim=mc.head_dim, scale=scale, eps=eps, act=act)
                la += 1
            else:
                p = {k: f32(M[k][lm]) for k in M}
                out, state, cols = _mamba(
                    h, p["norm"], p["in_proj"], p["conv_w"], p["conv_b"], p["dt_bias"], p["A_log"], p["D"],
                    p["gate_norm"], p["out_proj"], *carry[lm], H=H, P=P, G=G, N=N, eps=eps, act=act,
                    carry_every=mc.mamba_chunk_size if lower == "no_conv_carry" else 0,
                    bf16_state=lower == "bf16_state")
                left.append((state, cols))
                lm += 1
                del p
            h = h + rm * out
            if mc.num_experts:
                x, gates, idx = _route(h, f32(L["mlp_norm"][l]), f32(L["router"][l]), K=mc.num_experts_per_tok,
                                       eps=eps, act=act)
                out = _experts(x, gates, idx, L, l, first, n_held, lower == "all_experts", act)
            else:
                x = _act(_rms(h, f32(L["mlp_norm"][l]), eps), act)
                out = _swiglu(x, *(f32(L[k][l]) for k in ("w_gate", "w_up", "w_down")), act=act)
            if mc.shared_intermediate_size:
                out = out + _swiglu(x, *(f32(L[k][l]) for k in ("shared_gate", "shared_up", "shared_down")), act=act)
            h = h + rm * out
        return h, left

    zeros = [(jnp.zeros((H, P, N), F32), jnp.zeros((Kc - 1, mc.mamba_conv_dim), F32))] * mc.num_mamba_layers
    out = []
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        head = params["lm_head"].astype(F32) if "lm_head" in params else embed.T
        carry = zeros
        if lower == "stale_state":  # what the last sequence leaves is what the first one finds
            _, carry = run(seqs[-1], zeros)
        for seq, wanted in zip(seqs, positions):
            h, left = run(seq, carry)
            if lower == "stale_state":
                carry = left
            h = _rms(h[jnp.asarray(np.asarray(wanted, np.int32))], f32(params["final_norm"]), eps)
            out.append(np.asarray((h @ head) / float(mc.logits_scaling)))
    return out
