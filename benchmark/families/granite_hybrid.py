"""The ``granite_hybrid`` family: GraniteMoeHybrid decoders (Mamba-2
state-space mixers around attention layers, sparse experts of which this chip
holds a share, a shared expert), served by ``dynamo_tpu/engine/models/hybrid.py``
through ``ModelConfig.layer_types``.

Everything of the benchmark that depends on this architecture: the mapping
from the configuration file's Hugging Face keys, the parameter tree, the
output check's walk through the step programs on the paged pool and the state
slots beside it, the plain reference (``granite_hybrid_reference.py``, beside
this file) and the count of what a decode step needs.

Weights. One layer (for experts: one expert) is drawn at a time inside
``lax.map``, normal at 1/sqrt(fan-in) (0.02 for the tied embedding), bf16, norm
weights 1 + 0.1 N(0, 1). The router is drawn at eight times that scale, as the
``llama`` family's (logits peaked as a trained router's: a tie between the
tenth and the eleventh expert would route a token differently in bf16 and in
float32). The state-space constants are drawn so that a state neither forgets
in three tokens nor never: ``A`` uniform in [1, 8], the step ``dt`` (through
``dt_bias`` = softplus^-1) log-uniform in [1e-3, 2e-2], so that with the
token's own part of ``dt`` (in_proj's columns, spread ~1 before the softplus:
a factor e either way) a head's decay ``exp(dt A)`` lies in 0.90-0.999 for
most heads and reaches 0.65 for the fastest; ``D`` = 1; the convolution's
taps at 1/sqrt(d_conv), its bias 0.1 N(0, 1). The ``B`` and ``C`` columns of
``in_proj`` are drawn at ``BC_SCALE`` times 1/sqrt(fan-in): at 1 the state's
part of a head's output is a fifteenth of the skip ``D x``, and a slot that was
not zeroed (the control ``stale_state``) would hide inside the limits.

Output check. The sequences of ``parity.sample_inputs`` go through the
program's own step programs in the order a scheduler would, on a pool and
slot arrays of the sizes the scheduler makes (the spec's ``num_blocks`` and
``max_running`` + 1: the step programs are compiled on the arrays the window
times), with the kernels "auto" resolves to on this backend and the served
shapes. The decode bucket is full: beside the compared sequences ride live
ones that are not compared, on the low slots and blocks; the compared ones
are spread down from the highest slot and block and over the bucket's lanes:

0. the sequences that are not compared are prefilled, each into its slot;
   one more (the first prompt reversed) is prefilled into the first
   sequence's slot and leaves; the first sequence then takes that slot
   (``hybrid.open_slot`` zeroes it: with ``fault`` it does not);
1. ``hybrid.prefill`` of the first sequence (every position's logits);
2. one ``hybrid.mixed_step`` per chunk of each later prompt (the chunked scan
   on the prompt's slot, state and convolution columns carried from chunk to
   chunk, the last chunk partly padding), the sequences already admitted
   riding as decode rows (the single-step recurrence), teacher-forced;
3. ``hybrid.decode_multi`` windows over all sequences (the slot arrays carried
   through the window's loop).

Groups name what a position exercised: ``prefill`` (a slot another sequence
just left), ``chunk_first`` (a prompt's first chunk), ``chunk_carried`` (a later
chunk: state and columns carried in), ``mixed_decode`` (a row riding a mixed
step), ``window_s<i>``.

Counts. What the algorithm needs: bf16 weights as stored, of the experts only
those the step's rows visited (from the program's step entries; where none is
given, the expected number under uniform routing), the recurrent state and
the convolution columns of each row read and written, the one attention
layer's KV rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.families.granite_hybrid_reference import CONTROLS, forward as reference_forward
from benchmark.parity import pieces
from benchmark.roofline import _bytes_of
from benchmark.weights import seed_key

__all__ = ["model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost"]

BC_SCALE = 4.0


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` from the configuration file's Hugging
    Face keys, as run: the first ``num_hidden_layers`` of the published
    ``layer_types``, ``num_local_experts`` experts held of the router's
    ``deployment_experts.routed``."""
    from dynamo_tpu.engine.config import ModelConfig

    if cfg["model_type"] != "granitemoehybrid" or cfg["position_embedding_type"] != "nope":
        raise ValueError("the granite_hybrid family serves model_type granitemoehybrid without rotary positions")
    if cfg["normalization_function"] != "rmsnorm" or cfg["hidden_act"] != "silu" or not cfg["mamba_conv_bias"] \
            or cfg["mamba_proj_bias"] or cfg["attention_bias"]:
        raise ValueError("the granite_hybrid family serves rmsnorm, silu, a biased convolution and no other bias")
    heads, eng, L = cfg["num_attention_heads"], cfg["engine"], cfg["num_hidden_layers"]
    share = cfg["deployment_experts"]
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(min(eng.get("max_seq_len", cfg["max_position_embeddings"]), cfg["max_position_embeddings"])),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=eng.get("dtype", "bfloat16"),
        block_size=int(eng.get("block_size", 16)),
        attention_impl=eng.get("attention_impl", "auto"),
        prefill_impl=eng.get("prefill_impl", "auto"),
        num_experts=int(share["routed"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        num_experts_held=int(cfg["num_local_experts"]),
        first_expert_held=int(share["first_held"]),
        shared_intermediate_size=int(cfg["shared_intermediate_size"]),
        layer_types=tuple(cfg["layer_types"][:L]),
        mamba_d_state=int(cfg["mamba_d_state"]),
        mamba_d_conv=int(cfg["mamba_d_conv"]),
        mamba_n_heads=int(cfg["mamba_n_heads"]),
        mamba_d_head=int(cfg["mamba_d_head"]),
        mamba_n_groups=int(cfg["mamba_n_groups"]),
        mamba_expand=int(cfg["mamba_expand"]),
        mamba_chunk_size=int(cfg["mamba_chunk_size"]),
        use_rope=False,
        attention_scale=float(cfg["attention_multiplier"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
    )


def make_params(mc, seed: int, dtype=jnp.bfloat16):
    """The parameter tree ``TpuEngine.build(params=...)`` takes for ``mc``
    (``hybrid.init_params``'s layout: ``layers`` the FFN of every layer,
    ``attn`` and ``mamba`` the mixers of the layers of each kind)."""
    D, F, Fs, E = mc.hidden_size, mc.intermediate_size, mc.shared_intermediate_size, mc.experts_held
    H, di, cd, K, GN = mc.mamba_n_heads, mc.mamba_d_inner, mc.mamba_conv_dim, mc.mamba_d_conv, mc.mamba_n_groups * mc.mamba_d_state

    def mat(key, fan_in, fan_out, scale=1.0):
        return (jax.random.normal(key, (fan_in, fan_out), jnp.float32) * (scale * fan_in ** -0.5)).astype(dtype)

    def norm(key, n=D):
        return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)

    def ffn(key):
        ks = jax.random.split(key, 8)
        out = {"mlp_norm": norm(ks[0]), "router": mat(ks[1], D, mc.num_experts, 8.0),
               "shared_gate": mat(ks[5], D, Fs), "shared_up": mat(ks[6], D, Fs), "shared_down": mat(ks[7], Fs, D)}
        for name, k, (a, b) in (("w_gate", ks[2], (D, F)), ("w_up", ks[3], (D, F)), ("w_down", ks[4], (F, D))):
            out[name] = lax.map(lambda kk, a=a, b=b: mat(kk, a, b), jax.random.split(k, E))
        return out

    def attention(key):
        ks = jax.random.split(key, 5)
        return {"attn_norm": norm(ks[0]), "wq": mat(ks[1], D, mc.q_size), "wk": mat(ks[2], D, mc.kv_size),
                "wv": mat(ks[3], D, mc.kv_size), "wo": mat(ks[4], mc.q_size, D)}

    def mamba(key):
        ks = jax.random.split(key, 12)
        dt = jnp.exp(jax.random.uniform(ks[0], (H,), minval=jnp.log(1e-3), maxval=jnp.log(2e-2)))
        in_proj = jnp.concatenate([mat(ks[1], D, di), mat(ks[2], D, di), mat(ks[3], D, 2 * GN, BC_SCALE),
                                   mat(ks[4], D, H)], axis=1)  # z | x | B C | dt
        return {
            "norm": norm(ks[5]), "in_proj": in_proj,
            "conv_w": (jax.random.normal(ks[6], (K, cd), jnp.float32) * K ** -0.5).astype(dtype),
            "conv_b": (0.1 * jax.random.normal(ks[7], (cd,), jnp.float32)).astype(dtype),
            "dt_bias": jnp.log(jnp.expm1(dt)).astype(jnp.float32),
            "A_log": jnp.log(jax.random.uniform(ks[8], (H,), minval=1.0, maxval=8.0)),
            "D": jnp.ones((H,), jnp.float32),
            "gate_norm": norm(ks[9], di), "out_proj": mat(ks[10], di, D),
        }

    @jax.jit
    def build(key):
        k_embed, k_ffn, k_attn, k_mamba, k_norm = jax.random.split(key, 5)
        return {
            "embed": (jax.random.normal(k_embed, (mc.vocab_size, D), jnp.float32) * 0.02).astype(dtype),
            "final_norm": norm(k_norm),
            "layers": lax.map(ffn, jax.random.split(k_ffn, mc.num_layers)),
            "attn": lax.map(attention, jax.random.split(k_attn, mc.num_attention_layers)),
            "mamba": lax.map(mamba, jax.random.split(k_mamba, mc.num_mamba_layers)),
        }

    return build(seed_key(seed))


def program_logits(params, mc, spec: dict, lens, prompts, forced, fault: bool = False):
    """Runs the programs. Returns ``(rows, sampled, sampled_is_argmax)``:
    ``rows`` is a list of ``(group, sequence, position, logits [V])`` and
    ``sampled[i]`` the ids the windows fed back for sequence ``i``. With
    ``fault`` the first sequence takes its slot as the sequence before it left
    it (tied to its table, never zeroed): the control of ``group_rel_err``."""
    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import hybrid

    cfg = mc
    use_flash = hybrid.resolve_prefill_impl(cfg) == "flash"
    chunk, window, windows, batch = (int(spec[k]) for k in ("chunk", "window", "windows", "decode_bucket"))
    num_blocks, slots = int(spec["num_blocks"]), int(spec["max_running"]) + 1  # the pool and the slots the scheduler makes
    n, bs = len(lens), cfg.block_size
    longest = max(lens[i] + len(forced[i]) for i in range(n)) + windows * window
    W = 1 << (-(-(longest + 1) // bs) - 1).bit_length()  # table width: a power of two, as the scheduler's rungs
    # The bucket is full: beside the n compared sequences, batch - n live ones that are not compared (short prompts cut
    # from the compared ones, fed their own samples in the windows). They take the low slots and blocks and the compared
    # ones are spread down from the highest, over the bucket's lanes too: block 0 and slot 0 are scratch.
    fill, steps = batch - n, sum(len(pieces(m, chunk)) for m in lens[1:])
    fill_len = max(4, chunk // 8)
    fill_w = -(-(fill_len + steps + windows * window + 1) // bs)
    if n > batch or batch > slots - 1 or 1 + fill * fill_w + n * W > num_blocks or fill_w > W:
        raise ValueError("the compared sequences and a full bucket do not fit the stated pool and slots")
    lane = [(2 * i + 1) * batch // (2 * n) for i in range(n)]
    slot = [slots - 1 - i * ((slots - 2) // n) for i in range(n)]
    fill_lane = [b for b in range(batch) if b not in lane]
    fill_slot = [s for s in range(1, slots) if s not in slot][:fill]
    tables = np.zeros((batch, W), np.int32)
    for i in range(n):
        tables[lane[i]] = num_blocks - (i + 1) * W + np.arange(W)
    for f, b in enumerate(fill_lane):
        tables[b, :fill_w] = 1 + f * fill_w + np.arange(fill_w)
    stream = np.concatenate(prompts)
    cache = KvCacheArrays.create(cfg, num_blocks, dtype=params["embed"].dtype, num_slots=slots)  # as the engine makes them

    prefill = jax.jit(
        lambda p, k, v, t, vl, bt: hybrid.prefill(
            p, cfg, k, v, t, vl, jnp.int32(0), bt, all_logits=True, use_flash=use_flash, has_prefix=False)[:3],
        donate_argnums=(1, 2),
    )
    mixed = jax.jit(
        lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, hp: hybrid.mixed_step(
            p, cfg, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, use_flash=use_flash, has_prefix=hp)[:3],
        donate_argnums=(1, 2), static_argnums=(11,),
    )
    multi = jax.jit(
        lambda p, k, v, t, pos, bt, act, te, tk, tp, key: hybrid.decode_multi(
            p, cfg, k, v, t, pos, bt, act, te, tk, tp, key, window, return_logits=True)[:4],
        donate_argnums=(1, 2),
    )
    open_slot = jax.jit(hybrid.open_slot, donate_argnums=(0, 1))

    def whole(k, v, tokens, table):
        toks = np.zeros((chunk,), np.int32)
        toks[: len(tokens)] = tokens
        return prefill(params, k, v, jnp.asarray(toks), jnp.int32(len(tokens)), jnp.asarray(table))

    k, v = cache.k, cache.v
    d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
    for f, b in enumerate(fill_lane):
        k, v = open_slot(k, v, jnp.int32(tables[b][0]), jnp.int32(fill_slot[f]))
        _, k, v = whole(k, v, stream[f * fill_len:(f + 1) * fill_len], tables[b])
        d_tok[b], d_pos[b], d_act[b] = stream[-1 - f], fill_len, True
    # A sequence that leaves: its state and columns stay in the first sequence's slot behind it.
    k, v = open_slot(k, v, jnp.int32(tables[lane[0]][0]), jnp.int32(slot[0]))
    _, k, v = whole(k, v, prompts[0][::-1], tables[lane[0]])
    for i in range(n):
        if not (fault and i == 0):
            k, v = open_slot(k, v, jnp.int32(tables[lane[i]][0]), jnp.int32(slot[i]))

    lg, k, v = whole(k, v, prompts[0], tables[lane[0]])
    lg = np.asarray(lg)
    rows = [("prefill", 0, t, lg[t]) for t in range(lens[0])]

    fed = [0] * n  # forced tokens each sequence has consumed
    for j in range(1, n):
        for start, length in pieces(lens[j], chunk):
            toks = np.zeros((chunk,), np.int32)
            toks[:length] = prompts[j][start:start + length]
            for i in range(j):
                d_tok[lane[i]], d_pos[lane[i]], d_act[lane[i]] = forced[i][fed[i]], lens[i] + fed[i], True
            d_tab = np.where(d_act[:, None], tables, 0)  # a row not yet admitted: a table of zeros, the scratch slot
            lg, k, v = mixed(params, k, v, jnp.asarray(toks), jnp.int32(length), jnp.int32(start),
                             jnp.asarray(tables[lane[j]]), jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(d_tab),
                             jnp.asarray(d_act), start > 0)
            lg = np.asarray(lg)
            rows.append(("chunk_carried" if start > 0 else "chunk_first", j, start + length - 1, lg[0]))
            for i in range(j):
                rows.append(("mixed_decode", i, int(d_pos[lane[i]]), lg[1 + lane[i]]))
                fed[i] += 1
            for f, b in enumerate(fill_lane):  # the rows beside them go on, on tokens of the stream
                d_tok[b], d_pos[b] = stream[(f + 7 * int(d_pos[b])) % len(stream)], d_pos[b] + 1

    for i in range(n):
        d_tok[lane[i]], d_pos[lane[i]], d_act[lane[i]] = forced[i][fed[i]], lens[i] + fed[i], True
    sampled = [[] for _ in range(n)]
    is_argmax = True
    greedy = (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    for _ in range(windows):
        out, lg, k, v = multi(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(tables),
                              jnp.asarray(d_act), *greedy, jax.random.PRNGKey(0))
        out, lg = np.asarray(out), np.asarray(lg)
        for i in range(n):
            rows += [(f"window_s{i}", i, int(d_pos[lane[i]]) + s, lg[s, lane[i]]) for s in range(window)]
            sampled[i] += out[:, lane[i]].tolist()
        is_argmax = is_argmax and bool(np.array_equal(out[:, lane], np.argmax(lg[:, lane], axis=-1)))
        d_tok, d_pos = out[-1].astype(np.int32), d_pos + window * d_act.astype(np.int32)
    del k, v, cache
    return rows, sampled, is_argmax


# --- what a step needs ------------------------------------------------------------


def _sizes(cfg: dict) -> dict:
    D, H, P, N, G = (cfg[k] for k in ("hidden_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups"))
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    di, cd = H * P, H * P + 2 * G * N
    return {
        "D": D, "H": H, "P": P, "N": N, "di": di, "cd": cd, "K": cfg["mamba_d_conv"],
        "La": kinds.count("attention"), "Lm": kinds.count("mamba"), "L": len(kinds),
        "q": cfg["num_attention_heads"] * hd, "kv": cfg["num_key_value_heads"] * hd, "hd": hd,
        "mamba_params": D * (2 * di + 2 * G * N + H) + di * D,  # in_proj, out_proj
        "mamba_small": cfg["mamba_d_conv"] * cd + cd + 3 * H + di + D,  # conv, bias, dt_bias/A_log/D, two norms
        "expert_params": 3 * D * cfg["intermediate_size"],
        "shared_params": 3 * D * cfg["shared_intermediate_size"],
        "router_params": D * cfg["deployment_experts"]["routed"],
    }


def state_row_bytes(cfg: dict) -> float:
    """Recurrent state (float32) and convolution columns (bf16) of one
    sequence in ONE state-space layer."""
    s = _sizes(cfg)
    return s["H"] * s["P"] * s["N"] * _bytes_of("float32") + (s["K"] - 1) * s["cd"] * _bytes_of("bfloat16")


def ssm_update_cost(cfg: dict, rows: float) -> dict:
    """FLOPs and bytes of ONE launch of the kernel ``ssm_update_rows`` (one
    state-space layer, ``rows`` sequences): each row's float32 state read and
    written, its ``x``, ``B``, ``C`` and step in and its ``y`` out (float32);
    decay, outer product and add, the reduction with ``C``. The convolution
    columns are not the kernel's."""
    s, f32 = _sizes(cfg), _bytes_of("float32")
    state = s["H"] * s["P"] * s["N"]
    small = 2 * s["H"] * s["P"] + 2 * s["N"] + s["H"]
    return {"flops": rows * 6.0 * state, "bytes": rows * (2.0 * state + small) * f32}


def experts_reached(cfg: dict, rows: float) -> float:
    """Expected number of HELD experts a layer's ``rows`` tokens visit when
    each picks ``num_experts_per_tok`` of the router's experts uniformly."""
    routed, held, k = cfg["deployment_experts"]["routed"], cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return held * (1.0 - (1.0 - k / routed) ** rows)


def decode_step_cost(cfg: dict, weight_dtype: str, rows: float, ctx_tokens: float, experts_visited=None) -> dict:
    """FLOPs and bytes of ONE decode step over ``rows`` sequences whose
    contexts sum to ``ctx_tokens`` tokens. ``experts_visited`` is the number of
    (layer, held expert) pairs the step's rows fell on, summed over layers
    (the program's step entries carry it); None: the expected number under
    uniform routing. ``weight_dtype`` is the compute type: bf16 only."""
    if weight_dtype == "int8":
        raise ValueError("the granite_hybrid family counts bf16 weights")
    s, act = _sizes(cfg), _bytes_of("bfloat16")
    D, V, L, La, Lm = s["D"], cfg["vocab_size"], s["L"], s["La"], s["Lm"]
    held_share = cfg["num_local_experts"] / cfg["deployment_experts"]["routed"]
    if experts_visited is None:
        experts_visited = L * experts_reached(cfg, rows)
    attn_params = D * s["q"] + 2 * D * s["kv"] + s["q"] * D
    expert_bytes = experts_visited * s["expert_params"] * act
    mixer_bytes = (Lm * (s["mamba_params"] + s["mamba_small"]) + La * (attn_params + D)) * act
    ffn_bytes = L * (s["shared_params"] + s["router_params"] + D) * act
    head_bytes = (D * V + D) * act  # the tied embedding as the head, and the final norm
    weight_bytes = expert_bytes + mixer_bytes + ffn_bytes + head_bytes
    state_bytes = 2.0 * rows * Lm * state_row_bytes(cfg)  # read and written
    kv_bytes = La * 2.0 * s["kv"] * act * (ctx_tokens + rows)  # every attended row read, one written a sequence
    io_bytes = rows * (D * act + V * 4.0)  # embedding rows in, float32 logits out
    # Matmuls of every row; of the routed experts, the K * held/routed assignments a row has here on average.
    per_row = 2.0 * (Lm * s["mamba_params"] + La * attn_params + L * (s["shared_params"] + s["router_params"])
                     + L * cfg["num_experts_per_tok"] * held_share * s["expert_params"] + D * V)
    ssm_flops = rows * Lm * 6.0 * s["H"] * s["P"] * s["N"]  # decay, outer product and add, the reduction with C
    flops = rows * per_row + ssm_flops + La * 4.0 * s["q"] * ctx_tokens
    return {"flops": flops, "bytes": weight_bytes + state_bytes + kv_bytes + io_bytes,
            "weight_bytes": weight_bytes, "expert_bytes": expert_bytes, "state_bytes": state_bytes, "kv_bytes": kv_bytes}
