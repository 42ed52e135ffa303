"""The ``llama`` family: Llama, Mistral and Mixtral decoders, served by
``dynamo_tpu/engine/models/llama.py``.

Everything of the benchmark that depends on this architecture: the mapping
from the configuration file's Hugging Face keys, the parameter tree, the
output check's walk through the step programs on the paged ``(k, v)`` pool,
the plain reference (``llama_reference.py``, beside this file) and the count
of what a decode step needs.

Weights. The program's own ``init_params`` draws every stack in float32 on the
default device before anything is quantised: Mistral-7B needs 14.5 GB in bf16
alone and does not fit a 16 GB chip that way. Here one layer (for experts: one
expert) is drawn at a time inside ``lax.map``, scaled like ``init_params``
(normal, 1/sqrt(fan-in); 0.02 for the embedding and the head), and for
``weight_dtype == "int8"`` turned into int8 codes with one float32 scale per
output channel (symmetric, amax/127) before the next is drawn. The program and
the float32 reference are both handed this tree; the reference dequantises the
same codes. Only the container type ``QuantW`` is the program's.

Output check. A seeded handful of sequences is taken through the program's own
step programs in the order a scheduler would, on one paged cache, with the
kernels "auto" resolves to on this backend (on a TPU: the ragged megakernel
and the flash kernel) and the served shapes (the configuration's chunk, decode
bucket and window):

1. ``llama.prefill`` of the first sequence (every position's logits);
2. one ``llama.mixed_step`` per chunk of each later prompt, the sequences
   already in the cache riding along as decode rows (teacher-forced from the
   seed). A prompt longer than the chunk takes several steps, all but the
   first with a cached prefix (``has_prefix=True``);
3. ``llama.decode_multi`` windows over all sequences: on-device greedy
   sampling, the window-local KV and its fused scatter, the second window
   reading what the first wrote.

Counts. The work the algorithm needs, not what a program happens to move:
weights as stored (int8 codes plus float32 scales for W8, bf16 otherwise), the
bf16 head, the embedding rows read, the KV rows of the contexts actually in
the step and the rows it writes; for sparse experts only the experts the
step's tokens are expected to reach.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.families.llama_reference import CONTROLS, forward as reference_forward
from benchmark.parity import pieces
from benchmark.roofline import _bytes_of
from benchmark.weights import quantize, seed_key

__all__ = ["model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost"]


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` from the configuration file's Hugging
    Face keys, as run."""
    from dynamo_tpu.engine.config import ModelConfig

    heads = cfg["num_attention_heads"]
    eng = cfg["engine"]
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(min(eng.get("max_seq_len", cfg["max_position_embeddings"]), cfg["max_position_embeddings"])),
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=eng.get("dtype", "bfloat16"),
        num_experts=int(cfg.get("num_local_experts", 0)),
        num_experts_per_tok=int(cfg.get("num_experts_per_tok", 0)),
        weight_dtype=eng.get("weight_dtype", "auto"),
        kv_cache_dtype=eng.get("kv_cache_dtype", "auto"),
        block_size=int(eng.get("block_size", 16)),
    )


def make_params(mc, seed: int, dtype=jnp.bfloat16):
    """The parameter tree ``TpuEngine.build(params=...)`` takes for ``mc``."""
    from dynamo_tpu.engine.quant import QuantW

    int8 = mc.weight_dtype == "int8"
    D, F, E = mc.hidden_size, mc.intermediate_size, mc.num_experts

    def mat(key, fan_in, fan_out):
        w = jax.random.normal(key, (fan_in, fan_out), jnp.float32) * (fan_in ** -0.5)
        return quantize(w) if int8 else w.astype(dtype)

    def norm(key):
        return (1.0 + 0.1 * jax.random.normal(key, (D,), jnp.float32)).astype(dtype)

    def layer(key):
        ks = jax.random.split(key, 10)
        out = {
            "attn_norm": norm(ks[0]), "mlp_norm": norm(ks[1]),
            "wq": mat(ks[2], D, mc.q_size), "wk": mat(ks[3], D, mc.kv_size),
            "wv": mat(ks[4], D, mc.kv_size), "wo": mat(ks[5], mc.q_size, D),
        }
        if E == 0:
            out.update(w_gate=mat(ks[6], D, F), w_up=mat(ks[7], D, F), w_down=mat(ks[8], F, D))
        else:
            # Eight times init_params' scale: router logits of spread ~8, as a trained router's are
            # peaked. At spread ~1 the second and third experts of a token tie within bf16 noise in
            # ~5% of (token, layer) pairs, the program and the float32 reference then route a token
            # differently, and the output check reads 0.011 on one seed and 0.054 on the next
            # (my chip run, PR 24).
            out["router"] = (jax.random.normal(ks[9], (D, E), jnp.float32) * (8.0 * D ** -0.5)).astype(dtype)
            for name, k, (a, b) in (("w_gate", ks[6], (D, F)), ("w_up", ks[7], (D, F)), ("w_down", ks[8], (F, D))):
                out[name] = lax.map(lambda kk, a=a, b=b: mat(kk, a, b), jax.random.split(k, E))
        return out

    @jax.jit
    def build(key):
        k_embed, k_layers, k_head, k_norm = jax.random.split(key, 4)
        params = {
            "embed": (jax.random.normal(k_embed, (mc.vocab_size, D), jnp.float32) * 0.02).astype(dtype),
            "final_norm": norm(k_norm),
            "layers": lax.map(layer, jax.random.split(k_layers, mc.num_layers)),
        }
        if not mc.tie_word_embeddings:
            params["lm_head"] = (jax.random.normal(k_head, (D, mc.vocab_size), jnp.float32) * 0.02).astype(dtype)
        return params

    params = build(seed_key(seed))
    if int8:
        params["layers"] = {
            k: QuantW(*v) if isinstance(v, tuple) else v for k, v in params["layers"].items()
        }
    return params


def program_logits(params, mc, spec: dict, lens, prompts, forced, fault: bool = False):
    """Runs the programs. Returns ``(rows, sampled, sampled_is_argmax)``:
    ``rows`` is a list of ``(group, sequence, position, logits [V])`` and
    ``sampled[i]`` the ids the windows fed back for sequence ``i``. With
    ``fault`` every chunk with a cached prefix is handed the block table of
    the sequence before it: the control of ``group_rel_err``."""
    from dynamo_tpu.engine.config import resolve_moe_dispatch
    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import llama

    cfg = resolve_moe_dispatch(mc, 1)
    use_flash = llama.resolve_prefill_impl(cfg) == "flash"
    chunk, window, windows, batch = (int(spec[k]) for k in ("chunk", "window", "windows", "decode_bucket"))
    n, bs = len(lens), cfg.block_size
    if n > batch:
        raise ValueError("more sequences than decode lanes")
    longest = max(lens[i] + len(forced[i]) for i in range(n)) + windows * window
    W = 1 << (-(-(longest + 1) // bs) - 1).bit_length()  # table width: a power of two, as the scheduler's rungs
    cache = KvCacheArrays.create(cfg, 1 + n * W, dtype=jnp.bfloat16)  # block 0 is the scratch block
    tables = np.zeros((batch, W), np.int32)
    for row in range(n):
        tables[row] = 1 + row * W + np.arange(W)

    prefill = jax.jit(
        lambda p, k, v, t, vl, bt: llama.prefill(
            p, cfg, k, v, t, vl, jnp.int32(0), bt, all_logits=True, use_flash=use_flash, has_prefix=False),
        donate_argnums=(1, 2),
    )
    mixed = jax.jit(
        lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, hp: llama.mixed_step(
            p, cfg, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, use_flash=use_flash, has_prefix=hp),
        donate_argnums=(1, 2), static_argnums=(11,),
    )
    multi = jax.jit(
        lambda p, k, v, t, pos, bt, act, te, tk, tp, key: llama.decode_multi(
            p, cfg, k, v, t, pos, bt, act, te, tk, tp, key, window, return_logits=True),
        donate_argnums=(1, 2),
    )

    k, v = cache.k, cache.v
    rows = []
    toks = np.zeros((chunk,), np.int32)
    toks[: lens[0]] = prompts[0]
    lg, k, v = prefill(params, k, v, jnp.asarray(toks), jnp.int32(lens[0]), jnp.asarray(tables[0]))
    lg = np.asarray(lg)
    rows += [("prefill", 0, t, lg[t]) for t in range(lens[0])]

    fed = [0] * n  # forced tokens each sequence has consumed
    for j in range(1, n):
        for start, length in pieces(lens[j], chunk):
            toks = np.zeros((chunk,), np.int32)
            toks[:length] = prompts[j][start:start + length]
            d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
            for i in range(j):
                d_tok[i], d_pos[i], d_act[i] = forced[i][fed[i]], lens[i] + fed[i], True
            lg, k, v = mixed(params, k, v, jnp.asarray(toks), jnp.int32(length), jnp.int32(start),
                             jnp.asarray(tables[j - 1 if fault and start > 0 else j]), jnp.asarray(d_tok),
                             jnp.asarray(d_pos), jnp.asarray(tables),
                             jnp.asarray(d_act), start > 0)
            lg = np.asarray(lg)
            rows.append(("chunk_prefix" if start > 0 else "chunk_fresh", j, start + length - 1, lg[0]))
            for i in range(j):
                rows.append(("mixed_decode", i, int(d_pos[i]), lg[1 + i]))
                fed[i] += 1

    d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
    for i in range(n):
        d_tok[i], d_pos[i], d_act[i] = forced[i][fed[i]], lens[i] + fed[i], True
    sampled = [[] for _ in range(n)]
    is_argmax = True
    greedy = (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    for _ in range(windows):
        out, lg, k, v = multi(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(tables),
                              jnp.asarray(d_act), *greedy, jax.random.PRNGKey(0))
        out, lg = np.asarray(out), np.asarray(lg)
        for i in range(n):
            rows += [(f"window_s{i}", i, int(d_pos[i]) + s, lg[s, i]) for s in range(window)]
            sampled[i] += out[:, i].tolist()
        is_argmax = is_argmax and bool(np.array_equal(out[:, :n], np.argmax(lg[:, :n], axis=-1)))
        d_tok, d_pos = out[-1].astype(np.int32), d_pos + window * d_act.astype(np.int32)
    del k, v, cache
    return rows, sampled, is_argmax


def layer_shapes(cfg: dict) -> dict:
    """(fan_in, fan_out) of each matmul weight of one layer; expert weights once."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"attn": [(D, q), (D, kv), (D, kv), (q, D)], "mlp": [(D, F), (D, F), (F, D)]}


def experts_reached(n_experts: int, k: int, rows: float) -> float:
    """Expected number of distinct experts that ``rows`` tokens routed to ``k``
    of ``n_experts`` uniformly reach."""
    if n_experts == 0:
        return 1.0
    return n_experts * (1.0 - (1.0 - k / n_experts) ** rows)


def decode_step_cost(cfg: dict, weight_dtype: str, rows: float, ctx_tokens: float) -> dict:
    """FLOPs and bytes of ONE decode step over ``rows`` sequences whose
    contexts sum to ``ctx_tokens`` tokens. ``cfg`` holds the configuration
    file's Hugging Face keys; ``weight_dtype`` is "int8" or the compute type."""
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    E, K = cfg.get("num_local_experts", 0), cfg.get("num_experts_per_tok", 0)
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shapes = layer_shapes(cfg)
    act = _bytes_of("bfloat16")
    wb = _bytes_of("int8" if weight_dtype == "int8" else "bfloat16")

    def stored(shape):  # bytes of one weight as stored
        a, b = shape
        return a * b * wb + (b * 4.0 if weight_dtype == "int8" else 0.0)

    attn_w = sum(stored(s) for s in shapes["attn"])
    mlp_w_one = sum(stored(s) for s in shapes["mlp"])
    mlp_params_one = sum(a * b for a, b in shapes["mlp"])
    attn_params = sum(a * b for a, b in shapes["attn"])
    if E:
        mlp_w = mlp_w_one * experts_reached(E, K, rows) + D * E * act
        mlp_flops_per_row = 2.0 * (mlp_params_one * K + D * E)
    else:
        mlp_w = mlp_w_one
        mlp_flops_per_row = 2.0 * mlp_params_one
    kv_row = 2.0 * KVH * hd * act  # K and V of one token in one layer
    weight_bytes = L * (attn_w + mlp_w + 2 * D * act) + D * V * act + D * act
    kv_bytes = L * kv_row * (ctx_tokens + rows)  # read every context, write one row each
    io_bytes = rows * (D * act + V * 4.0)  # embedding rows in, float32 logits out
    flops = rows * (L * (2.0 * attn_params + mlp_flops_per_row) + 2.0 * D * V) \
        + L * 4.0 * H * hd * ctx_tokens  # scores and weighted values over the contexts
    return {"flops": flops, "bytes": weight_bytes + kv_bytes + io_bytes,
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}
