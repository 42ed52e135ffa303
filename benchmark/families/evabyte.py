"""The ``evabyte`` family: EvaByte, a byte-level decoder with EVA chunked
linear attention, served by ``dynamo_tpu/engine/models/llama.py`` with
``attention_kind="eva"``.

Everything of the benchmark that depends on this architecture: the mapping
from the configuration file's Hugging Face keys, the parameter tree, the
output check's walk through the step programs on the paged pool, the plain
reference (``evabyte_reference.py``, beside this file) and the count of what a
decode step and a roll need.

Weights. One layer is drawn at a time inside ``lax.map``, scaled like the
program's ``init_params`` (normal, 1/sqrt(fan-in); 0.02 for the embedding and
the head of 8 x 320 columns), bf16 as published. Norm weights are stored
around zero (the layer multiplies by ``1 + g``) with the spread the ``llama``
family gives its norms (0.1). The pooling queries ``mu`` and ``phi`` are drawn
at ``POOL_SCALE``: see there.

Output check. The sequences of ``parity.sample_inputs`` go through the
program's own step programs in the order a scheduler would, on one paged pool
in which a sequence's table is laid out by ``kv_cache.cache_rows`` (summaries
of rolled windows, then the current window's rows), with the kernels "auto"
resolves to on this backend and the served shapes:

1. ``llama.prefill`` of the first sequence (every position's logits);
2. one ``llama.mixed_step`` per chunk of each later prompt (chunks never
   straddle a window: the chunk divides it), the sequences already in the
   cache riding along as decode rows, teacher-forced from the seed;
3. ``llama.eva_roll`` whenever a sequence's next write opens a window: between
   chunks of a long prompt, and between two decode steps of a riding row;
4. ``llama.decode_multi`` windows over all sequences. A row stops at its
   window boundary inside the program (its later steps write nothing); the
   walk takes only the tokens before it, rolls, and goes on, as the scheduler
   does.

Groups name what a position's attention saw: ``prefill``, ``chunk_fresh``
(no prefix), ``chunk_window`` (exact keys of the prompt's first window),
``chunk_summaries`` (summaries, and from the second chunk of a window on a
partly filled window beside them), ``mixed_decode`` (a riding row inside its
first window, the step that completes it included), ``decode_rolled`` (a
riding row after a roll, the first step after it included), ``window_s<i>``.
With ``fault`` no roll program runs: the tables move on as if it had, so the
rows where summaries belong hold a finished window's first exact keys.

Counts. What the algorithm needs: bf16 weights as stored, the first
prediction head's columns of ``lm_head``, the cache rows a step attends (for
this family ``ctx_tokens`` means *attended rows*, ``cache_rows`` of each
context, not its bytes) and the rows it writes; a roll reads a window's rows
and writes its summaries, in every layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.families.evabyte_reference import CONTROLS, forward as reference_forward
from benchmark.parity import pieces
from benchmark.roofline import _bytes_of
from benchmark.weights import seed_key

__all__ = ["model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost"]

# Spread of the pooling queries mu and phi (init_params draws them at 1.0 for a tiny head of 16). A cached key's
# entries are ~N(0, 1) (normed input, 1/sqrt(fan-in) weights), so a pooling logit mu . k has spread POOL_SCALE *
# sqrt(head_dim) = 2.8 at head_dim 128: among a chunk's 16 members the largest weight is several times the mean, and
# pooling by a plain mean (the control ``uniform_pool``) moves every position that attends a summary. At spread >> 3
# pooling would pick one member, and a near-tie would make the summaries discontinuous in the keys' rounding.
POOL_SCALE = 0.25


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` from the configuration file's Hugging
    Face keys, as run."""
    from dynamo_tpu.engine.config import ModelConfig

    if cfg["attention_class"] != "eva":
        raise ValueError(f"the evabyte family serves attention_class 'eva', not {cfg['attention_class']!r}")
    heads, eng = cfg["num_attention_heads"], cfg["engine"]
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
        weight_dtype=eng.get("weight_dtype", "auto"),
        kv_cache_dtype=eng.get("kv_cache_dtype", "auto"),
        block_size=int(eng.get("block_size", 16)),
        attention_impl=eng.get("attention_impl", "auto"),
        prefill_impl=eng.get("prefill_impl", "auto"),
        attention_kind="eva",
        window_size=int(cfg["window_size"]),
        chunk_size=int(cfg["chunk_size"]),
        norm_unit_offset=bool(cfg["norm_add_unit_offset"]),
        residual_fp32=bool(cfg["fp32_skip_add"]),
        num_pred_heads=int(cfg["num_pred_heads"]),
    )


def make_params(mc, seed: int, dtype=jnp.bfloat16):
    """The parameter tree ``TpuEngine.build(params=...)`` takes for ``mc``."""
    D, F = mc.hidden_size, mc.intermediate_size

    def mat(key, fan_in, fan_out):
        return (jax.random.normal(key, (fan_in, fan_out), jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    def norm(key):  # g of 1 + g
        return (0.1 * jax.random.normal(key, (D,), jnp.float32)).astype(dtype)

    def pool(key):
        return (POOL_SCALE * jax.random.normal(key, (mc.num_kv_heads, mc.head_dim), jnp.float32)).astype(dtype)

    def layer(key):
        ks = jax.random.split(key, 11)
        return {
            "attn_norm": norm(ks[0]), "mlp_norm": norm(ks[1]),
            "wq": mat(ks[2], D, mc.q_size), "wk": mat(ks[3], D, mc.kv_size),
            "wv": mat(ks[4], D, mc.kv_size), "wo": mat(ks[5], mc.q_size, D),
            "w_gate": mat(ks[6], D, F), "w_up": mat(ks[7], D, F), "w_down": mat(ks[8], F, D),
            "eva_mu": pool(ks[9]), "eva_phi": pool(ks[10]),
        }

    @jax.jit
    def build(key):
        k_embed, k_layers, k_head, k_norm = jax.random.split(key, 4)
        return {
            "embed": (jax.random.normal(k_embed, (mc.vocab_size, D), jnp.float32) * 0.02).astype(dtype),
            "final_norm": norm(k_norm),
            "layers": lax.map(layer, jax.random.split(k_layers, mc.num_layers)),
            "lm_head": (jax.random.normal(k_head, (D, mc.vocab_size * mc.num_pred_heads), jnp.float32)
                        * 0.02).astype(dtype),
        }

    return build(seed_key(seed))


def program_logits(params, mc, spec: dict, lens, prompts, forced, fault: bool = False):
    """Runs the programs. Returns ``(rows, sampled, sampled_is_argmax)``:
    ``rows`` is a list of ``(group, sequence, position, logits [V])`` and
    ``sampled[i]`` the ids the windows fed back for sequence ``i``. With
    ``fault`` the roll program never runs (the tables roll all the same): the
    control of ``group_rel_err``."""
    from dynamo_tpu.engine.kv_cache import KvCacheArrays, cache_rows
    from dynamo_tpu.engine.models import llama

    cfg = mc
    use_flash = llama.resolve_prefill_impl(cfg) == "flash"
    chunk, window, windows, batch = (int(spec[k]) for k in ("chunk", "window", "windows", "decode_bucket"))
    n, bs, W, M = len(lens), cfg.block_size, cfg.window_size, cfg.summaries_per_window
    if n > batch:
        raise ValueError("more sequences than decode lanes")
    if W % chunk:
        raise ValueError("the chunk divides the window: a chunk never straddles a boundary")
    longest = max(lens[i] + len(forced[i]) for i in range(n)) + windows * window
    most_rows = M * (longest // W) + W  # summaries of every completed window, and one whole window
    Wt = 1 << (-(-most_rows // bs) - 1).bit_length()  # table width: a power of two, as the scheduler's rungs
    cache = KvCacheArrays.create(cfg, 1 + n * Wt, dtype=jnp.bfloat16)  # block 0 is the scratch block
    tables = np.zeros((batch, Wt), np.int32)
    for row in range(n):
        tables[row] = 1 + row * Wt + np.arange(Wt)

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
    roll = jax.jit(lambda p, k, v, t, r0: llama.eva_roll(p, cfg, k, v, t, r0), donate_argnums=(1, 2))
    nb = llama.eva_roll_blocks(cfg)

    k, v = cache.k, cache.v
    rolled = [0] * n  # windows of each sequence that are summaries by now

    def roll_before(i: int, position: int):
        """Sequence ``i`` is about to write ``position``: roll the window it
        completed, if that write opens another."""
        nonlocal k, v
        while position // W > rolled[i]:
            row0 = M * rolled[i]
            if not fault:
                table = np.zeros((nb,), np.int32)
                mine = tables[i][row0 // bs: row0 // bs + nb]
                table[: len(mine)] = mine
                k, v = roll(params, k, v, jnp.asarray(table), jnp.int32(row0))
            rolled[i] += 1

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
            roll_before(j, start)
            for i in range(j):
                d_tok[i], d_pos[i], d_act[i] = forced[i][fed[i]], lens[i] + fed[i], True
                roll_before(i, int(d_pos[i]))
            lg, k, v = mixed(params, k, v, jnp.asarray(toks), jnp.int32(length), jnp.int32(start),
                             jnp.asarray(tables[j]), jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(tables),
                             jnp.asarray(d_act), start > 0)
            lg = np.asarray(lg)
            group = "chunk_fresh" if start == 0 else "chunk_window" if start < W else "chunk_summaries"
            rows.append((group, j, start + length - 1, lg[0]))
            for i in range(j):
                rows.append(("mixed_decode" if d_pos[i] < W else "decode_rolled", i, int(d_pos[i]), lg[1 + i]))
                fed[i] += 1

    d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
    for i in range(n):
        d_tok[i], d_pos[i], d_act[i] = forced[i][fed[i]], lens[i] + fed[i], True
    sampled = [[] for _ in range(n)]
    is_argmax = True
    greedy = (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    for _ in range(windows):
        for i in range(n):
            roll_before(i, int(d_pos[i]))
        out, lg, k, v = multi(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(tables),
                              jnp.asarray(d_act), *greedy, jax.random.PRNGKey(0))
        out, lg = np.asarray(out), np.asarray(lg)
        for i in range(n):
            taken = min(window, W - int(d_pos[i]) % W)  # a row stops at its window boundary
            rows += [(f"window_s{i}", i, int(d_pos[i]) + s, lg[s, i]) for s in range(taken)]
            sampled[i] += out[:taken, i].tolist()
            is_argmax = is_argmax and bool(np.array_equal(out[:taken, i], np.argmax(lg[:taken, i], axis=-1)))
            d_tok[i], d_pos[i] = out[taken - 1, i], d_pos[i] + taken
    assert all(cache_rows(cfg, int(d_pos[i])) < Wt * bs for i in range(n))
    del k, v, cache
    return rows, sampled, is_argmax


def layer_params(cfg: dict) -> float:
    """Matmul parameters of one layer (MHA and SwiGLU)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return D * q + 2 * D * kv + q * D + 3.0 * D * F


def kv_row_bytes(cfg: dict) -> float:
    """K and V of one cache row in one layer (bf16)."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * cfg["num_key_value_heads"] * hd * _bytes_of("bfloat16")


def roll_cost(cfg: dict) -> dict:
    """FLOPs and bytes of ONE roll: in every layer a window's rows read, two
    pooling logits and two weighted sums per row, the summaries written."""
    L, W, M = cfg["num_hidden_layers"], cfg["window_size"], cfg["window_size"] // cfg["chunk_size"]
    width = kv_row_bytes(cfg) / _bytes_of("bfloat16")  # K and V elements of a row
    return {"flops": L * W * width * 4.0, "bytes": L * kv_row_bytes(cfg) * (W + M)}


def decode_step_cost(cfg: dict, weight_dtype: str, rows: float, ctx_tokens: float, rolls: float = 0.0) -> dict:
    """FLOPs and bytes of ONE decode step over ``rows`` sequences that attend
    ``ctx_tokens`` cache rows in all (for this family: ``cache_rows`` of each
    context, summaries and window keys, never the contexts' bytes), plus
    ``rolls`` rolls where the step carries them. ``weight_dtype`` is the
    compute type: this family serves bf16 weights only."""
    if weight_dtype == "int8":
        raise ValueError("the evabyte family counts bf16 weights")
    L, D, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    H = cfg["num_attention_heads"]
    act = _bytes_of("bfloat16")
    # Layer weights, two norms a layer, the final norm, and the next-byte head: the other prediction heads' columns
    # and the pooling queries (read by a roll, 2 * 32 * 128 a layer) are not a decode step's.
    weight_bytes = L * (layer_params(cfg) * act + 2 * D * act) + D * V * act + D * act
    kv_bytes = L * kv_row_bytes(cfg) * (ctx_tokens + rows)  # read every attended row, write one row each
    io_bytes = rows * (D * act + V * 4.0)  # embedding rows in, float32 logits out
    flops = rows * (L * 2.0 * layer_params(cfg) + 2.0 * D * V) + L * 4.0 * H * hd * ctx_tokens
    roll = roll_cost(cfg)
    return {"flops": flops + rolls * roll["flops"], "bytes": weight_bytes + kv_bytes + io_bytes + rolls * roll["bytes"],
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}
