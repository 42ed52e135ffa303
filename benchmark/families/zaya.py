"""The ``zaya`` family: ZAYA1 decoders (every layer attention in a compressed
latent behind two causal convolutions and a value shift, then top-1 of 16
experts or a skip choice behind a router MLP that carries its state from layer
to layer, joined to the stream by a learned residual merge), served by
``dynamo_tpu/engine/models/hybrid.py`` through ``ModelConfig.layer_types``
("cca").

Everything of the benchmark that depends on this architecture: the mapping
from the configuration file's Hugging Face keys, the parameter tree, the
output check's walk through the step programs on the paged pool and the
column slots beside it, the plain reference (``zaya_reference.py``, beside this
file) and the count of what a decode step needs.

Weights. One layer (for experts: one expert) is drawn at a time inside
``lax.map``, normal at 1/sqrt(fan-in) (0.02 for the tied embedding), bf16;
norm gains 1 + 0.1 N(0, 1); the convolutions' taps at 1/sqrt(taps x fan-in of
a channel), their biases 0.1 N(0, 1); the residual merge's gains 1 + 0.1
N(0, 1), its offsets 0.02 N(0, 1). The keys' temperatures are uniform in
[8, 16]: unit queries against unit keys of 128 lanes have cosines of spread
0.09, so scores spread 0.7-1.4 and a softmax over a thousand keys is neither
flat nor one-hot. The router (``ROUTER``): down-projection at 1/sqrt(fan-in),
its bias 0.1 N; the carried state's gain ``gamma`` 0.5 + 0.1 N (the state a
layer receives is a third of its input's power: leaving it out, the control
``no_router_carry``, turns half the choices); the MLP's two hidden matrices at
1.5/sqrt(256) and its output matrix at 4/sqrt(256), each with zero column
means (a GELU's output has a positive mean, and through raw columns that mean
becomes one constant offset a choice: some experts then never win), biases 0.1
N: by a NumPy study at these widths the chosen probability averages 0.62
(1/17 = 0.059), every one of the 17 choices takes 1.9-13% of the tokens in
every layer, the skip choice 6%; the balancing bias ``beta`` 0.02 N, small and
not zero: where two probabilities lie within 0.02 it picks the smaller, so the
choice and the weight can be told apart. The router's tensors past the
down-projection are float32, as the program computes them.

Output check. The sequences of ``parity.sample_inputs`` go through the
program's own four step programs in the order a scheduler would, on a pool and
slot arrays of the sizes the scheduler makes, with the kernels "auto" resolves
to on this backend and the served shapes. The decode bucket is full: beside
the compared sequences ride live ones that are not compared, on the low slots
and blocks; the compared ones are spread down from the highest slot and block
and over the bucket's lanes:

0. the sequences that are not compared are prefilled, each into its slot; into
   the slot of each sequence whose first positions are compared (the first,
   and those of three or more chunks) one more (that prompt's first chunk
   reversed) is prefilled and leaves; the sequence then takes that slot
   (``hybrid.open_slot`` zeroes it: with ``fault`` it does not);
1. ``hybrid.prefill`` of the first sequence, every position's logits;
2. each later prompt chunk by chunk. A prompt of three or more chunks goes
   through ``hybrid.prefill`` with every position's logits (its later chunks
   start from the slot's columns and the pool's rows), each chunk followed by
   one ``hybrid.decode`` step of the sequences already admitted; a shorter one
   through one ``hybrid.mixed_step`` a chunk, those sequences riding as decode
   rows. Teacher-forced;
3. ``hybrid.decode_multi`` windows over all sequences (the slot array carried
   through the window's loop).

A vocabulary of 262,272 makes a position's logits a megabyte, so of a
position-by-position prefill only some are compared: the first eight, every
``EVERY``-th after them, the last. With one expert a token, a choice that
rounding turns moves that position's logits by a tenth to a half, and on a
bfloat16 stream of twenty layers that happens to most positions (PERF.md
section 6, PR 41): a group's median is steady only where the group is large or
lies where choices seldom turn. So the groups are few: ``slot_head`` (the
first eight positions of each sequence whose slot another just left, 40 in
all at the configuration's draw of seven sequences: a column that was not
zeroed reaches positions 0 and 1 through the convolutions and the shift, and
the next six through a quarter or more of their attention; so early in a
sequence few choices have turned), ``chunk_head`` (the first two positions of
each chunk that starts from the slot, 16 in all there: every column reaches
them), ``body`` (every other position of a position-by-position prefill),
``rows`` (a length-1 row out of the slot in a decode or mixed step, and a mixed
step's chunk at its last position) and ``windows`` (the decode windows' rows).

Counts. What the algorithm needs: weights as stored (bf16; the router's MLP
float32), of the experts only those the step's rows visited (from the
program's step entries; where none is given, the expected number under uniform
routing), each row's pool rows read and one written in every layer, each
row's slot columns read and written in every layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.families.zaya_reference import CONTROLS, forward as reference_forward
from benchmark.parity import pieces
from benchmark.roofline import _bytes_of
from benchmark.weights import seed_key

__all__ = ["model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost"]

ROUTER = {"gamma": 0.5, "hidden": 1.5, "out": 4.0, "beta": 0.02}
TEMPERATURE = (8.0, 16.0)
HEAD, EVERY = 8, 16  # of a position-by-position prefill: the first HEAD positions, every EVERY-th after, the last


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` from the configuration file's Hugging
    Face keys, as run: the first ``num_hidden_layers`` of the published
    ``layer_types`` (``hybrid``: attention sublayer and expert sublayer)."""
    from dynamo_tpu.engine.config import ModelConfig

    L = cfg["num_hidden_layers"]
    kinds = set(cfg["layer_types"][:L])
    if cfg["model_type"] != "zaya" or kinds != {"hybrid"} or cfg["sliding_window"] is not None:
        raise ValueError("the zaya family serves model_type zaya with layer_types 'hybrid' and no sliding window")
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] or cfg["lm_head_bias"] or not cfg["tie_word_embeddings"]:
        raise ValueError("the zaya family serves silu experts, no bias in attention or head, a tied embedding")
    rope, eng = cfg["rope_parameters"]["hybrid"], cfg["engine"]
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["moe_intermediate_size"],
        rope_theta=float(rope["rope_theta"]),
        rope_fraction=float(rope["partial_rotary_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(min(eng.get("max_seq_len", cfg["max_position_embeddings"]), cfg["max_position_embeddings"])),
        tie_word_embeddings=True,
        dtype=eng.get("dtype", "bfloat16"),
        block_size=int(eng.get("block_size", 16)),
        attention_impl=eng.get("attention_impl", "auto"),
        prefill_impl=eng.get("prefill_impl", "auto"),
        num_experts=int(cfg["num_experts"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        layer_types=("cca",) * L,
        cca_time0=int(cfg["cca_time0"]),
        cca_time1=int(cfg["cca_time1"]),
        router_kind="zaya",
        router_hidden_size=int(cfg["router_hidden_size"]),
        moe_skip_choice=True,
        residual_merge=True,
    )


def make_params(mc, seed: int, dtype=None):
    """The parameter tree ``TpuEngine.build(params=...)`` takes for ``mc``
    (``hybrid.init_params``'s layout: ``layers`` the expert sublayer of every
    layer, ``cca`` the attention sublayer), in the type the configuration
    serves (``engine.dtype``: bfloat16; a rehearsal's float32)."""
    dtype = jnp.dtype(mc.dtype) if dtype is None else dtype
    D, F, E, r, n = mc.hidden_size, mc.intermediate_size, mc.num_experts, mc.router_hidden_size, mc.router_choices
    C, hd, f32 = mc.cca_channels, mc.head_dim, jnp.float32

    def mat(key, fan_in, fan_out, scale=1.0, dt=dtype):
        return (jax.random.normal(key, (fan_in, fan_out), f32) * (scale * fan_in ** -0.5)).astype(dt)

    def centred(key, fan_in, fan_out, scale):
        w = mat(key, fan_in, fan_out, scale, f32)
        return w - jnp.mean(w, axis=0, keepdims=True)

    def vec(key, width, spread, mean=0.0, dt=dtype):
        return (mean + spread * jax.random.normal(key, (width,), f32)).astype(dt)

    def merge(key):
        ks = jax.random.split(key, 4)
        return {"res_gx": vec(ks[0], D, 0.1, 1.0), "res_bx": vec(ks[1], D, 0.02),
                "res_gf": vec(ks[2], D, 0.1, 1.0), "res_bf": vec(ks[3], D, 0.02)}

    def ffn(key):
        ks = jax.random.split(key, 16)
        out = {
            "mlp_norm": vec(ks[0], D, 0.1, 1.0),
            "router_down": mat(ks[1], D, r), "router_down_b": vec(ks[2], r, 0.1, dt=f32),
            "router_gamma": vec(ks[3], r, 0.1, ROUTER["gamma"], f32), "router_norm": vec(ks[4], r, 0.1, 1.0, f32),
            "router_w1": centred(ks[5], r, r, ROUTER["hidden"]), "router_b1": vec(ks[6], r, 0.1, dt=f32),
            "router_w2": centred(ks[7], r, r, ROUTER["hidden"]), "router_b2": vec(ks[8], r, 0.1, dt=f32),
            "router_w3": centred(ks[9], r, n, ROUTER["out"]), "router_beta": vec(ks[10], n, ROUTER["beta"], dt=f32),
            **merge(ks[11]),
        }
        for name, k, (a, b) in (("w_gate", ks[12], (D, F)), ("w_up", ks[13], (D, F)), ("w_down", ks[14], (F, D))):
            out[name] = lax.map(lambda kk, a=a, b=b: mat(kk, a, b), jax.random.split(k, E))
        return out

    def attention(key):
        ks = jax.random.split(key, 9)
        return {
            "attn_norm": vec(ks[0], D, 0.1, 1.0), "w_in": mat(ks[1], D, C + mc.kv_size), "wo": mat(ks[2], mc.q_size, D),
            "conv0_w": (jax.random.normal(ks[3], (2, C), f32) * 2 ** -0.5).astype(dtype), "conv0_b": vec(ks[4], C, 0.1),
            "conv1_w": (jax.random.normal(ks[5], (C // hd, 2 * hd, hd), f32) * (2 * hd) ** -0.5).astype(dtype),
            "conv1_b": vec(ks[6], C, 0.1),
            "k_temp": jax.random.uniform(ks[7], (mc.num_kv_heads,), f32, *TEMPERATURE),
            **merge(ks[8]),
        }

    @jax.jit
    def build(key):
        k_embed, k_ffn, k_attn, k_norm = jax.random.split(key, 4)
        return {
            "embed": (jax.random.normal(k_embed, (mc.vocab_size, D), f32) * 0.02).astype(dtype),
            "final_norm": vec(k_norm, D, 0.1, 1.0),
            "layers": lax.map(ffn, jax.random.split(k_ffn, mc.num_layers)),
            "cca": lax.map(attention, jax.random.split(k_attn, mc.num_layers)),
        }

    return build(seed_key(seed))


def _kept(length: int, head: int) -> list:
    """Positions of a position-by-position prefill of ``length`` that are compared."""
    return sorted({*range(min(head, length)), *range(head, length, EVERY), length - 1})


def program_logits(params, mc, spec: dict, lens, prompts, forced, fault: bool = False):
    """Runs the programs. Returns ``(rows, sampled, sampled_is_argmax)``:
    ``rows`` is a list of ``(group, sequence, position, logits [V])`` and
    ``sampled[i]`` the ids the windows fed back for sequence ``i``. With
    ``fault`` the sequences whose first positions are compared take their slots
    as the sequence before left them (tied to the table, never zeroed): the
    control of ``group_rel_err``."""
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
    # from the compared ones, fed tokens of the stream). They take the low slots and blocks and the compared ones are
    # spread down from the highest, over the bucket's lanes too: block 0 and slot 0 are scratch.
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
        lambda p, k, v, t, vl, cl, bt, keep, hp: (lambda lg, k, v, _: (lg[keep], k, v))(*hybrid.prefill(
            p, cfg, k, v, t, vl, cl, bt, all_logits=True, use_flash=use_flash, has_prefix=hp)),
        donate_argnums=(1, 2), static_argnums=(8,),
    )
    mixed = jax.jit(
        lambda p, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, hp: hybrid.mixed_step(
            p, cfg, k, v, pt, pv, cl, ptab, dt, dpos, dtab, dact, use_flash=use_flash, has_prefix=hp)[:3],
        donate_argnums=(1, 2), static_argnums=(11,),
    )
    decode = jax.jit(lambda p, k, v, t, pos, bt, act: hybrid.decode(p, cfg, k, v, t, pos, bt, act)[:3], donate_argnums=(1, 2))
    multi = jax.jit(
        lambda p, k, v, t, pos, bt, act, te, tk, tp, key: hybrid.decode_multi(
            p, cfg, k, v, t, pos, bt, act, te, tk, tp, key, window, return_logits=True)[:4],
        donate_argnums=(1, 2),
    )
    open_slot = jax.jit(hybrid.open_slot, donate_argnums=(0, 1))

    def piece(k, v, tokens, start, table, keep):
        """One chunk through ``hybrid.prefill``; the logits of its positions ``keep`` (always ``HEAD + chunk // EVERY + 1`` of them)."""
        toks = np.zeros((chunk,), np.int32)
        toks[: len(tokens)] = tokens
        idx = np.zeros((HEAD + chunk // EVERY + 1,), np.int32)
        idx[: len(keep)] = keep
        lg, k, v = prefill(params, k, v, jnp.asarray(toks), jnp.int32(len(tokens)), jnp.int32(start), jnp.asarray(table),
                           jnp.asarray(idx), start > 0)
        return np.asarray(lg)[: len(keep)], k, v

    k, v = cache.k, cache.v
    d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)
    for f, b in enumerate(fill_lane):
        k, v = open_slot(k, v, jnp.int32(tables[b][0]), jnp.int32(fill_slot[f]))
        _, k, v = piece(k, v, stream[f * fill_len:(f + 1) * fill_len], 0, tables[b], [])
        d_tok[b], d_pos[b], d_act[b] = stream[-1 - f], fill_len, True
    # A sequence that leaves: its columns stay behind it in the slot of every sequence whose first positions are compared.
    heads = [0] + [j for j in range(1, n) if len(pieces(lens[j], chunk)) >= 3]
    for i in heads:
        k, v = open_slot(k, v, jnp.int32(tables[lane[i]][0]), jnp.int32(slot[i]))
        _, k, v = piece(k, v, prompts[i][:chunk][::-1], 0, tables[lane[i]], [])
    for i in range(n):
        if not (fault and i in heads):
            k, v = open_slot(k, v, jnp.int32(tables[lane[i]][0]), jnp.int32(slot[i]))

    keep = _kept(lens[0], HEAD)
    lg, k, v = piece(k, v, prompts[0], 0, tables[lane[0]], keep)
    rows = [("slot_head" if t < HEAD else "body", 0, t, lg[x]) for x, t in enumerate(keep)]

    fed = [0] * n  # forced tokens each sequence has consumed

    def ride(j):
        """The sequences admitted before ``j`` take their next forced token as decode rows."""
        for i in range(j):
            d_tok[lane[i]], d_pos[lane[i]], d_act[lane[i]] = forced[i][fed[i]], lens[i] + fed[i], True
        return np.where(d_act[:, None], tables, 0)  # a row not yet admitted: a table of zeros, the scratch slot

    def rode(j, group, lg):
        for i in range(j):
            rows.append((group, i, int(d_pos[lane[i]]), lg[lane[i]]))
            fed[i] += 1
        for f, b in enumerate(fill_lane):  # the rows beside them go on, on tokens of the stream
            d_tok[b], d_pos[b] = stream[(f + 7 * int(d_pos[b])) % len(stream)], d_pos[b] + 1

    for j in range(1, n):
        by_position = len(pieces(lens[j], chunk)) >= 3
        for start, length in pieces(lens[j], chunk):
            if by_position:
                keep = _kept(length, 2 if start else HEAD)
                lg, k, v = piece(k, v, prompts[j][start:start + length], start, tables[lane[j]], keep)
                rows += [("chunk_head" if start and t < 2 else "slot_head" if start + t < HEAD else "body", j, start + t, lg[x])
                         for x, t in enumerate(keep)]
                d_tab = ride(j)
                lg, k, v = decode(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(d_tab), jnp.asarray(d_act))
                rode(j, "rows", np.asarray(lg))
                continue
            toks = np.zeros((chunk,), np.int32)
            toks[:length] = prompts[j][start:start + length]
            d_tab = ride(j)
            lg, k, v = mixed(params, k, v, jnp.asarray(toks), jnp.int32(length), jnp.int32(start),
                             jnp.asarray(tables[lane[j]]), jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(d_tab),
                             jnp.asarray(d_act), start > 0)
            lg = np.asarray(lg)
            rows.append(("rows", j, start + length - 1, lg[0]))
            rode(j, "rows", lg[1:])

    for i in range(n):
        d_tok[lane[i]], d_pos[lane[i]], d_act[lane[i]] = forced[i][fed[i]], lens[i] + fed[i], True
    sampled = [[] for _ in range(n)]
    is_argmax = True
    greedy = (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    for _ in range(windows):
        out, lg, k, v = multi(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), jnp.asarray(tables),
                              jnp.asarray(d_act), *greedy, jax.random.PRNGKey(0))
        out, lg = np.asarray(out), np.asarray(lg[:, np.asarray(lane)])
        for x, i in enumerate(range(n)):
            rows += [("windows", i, int(d_pos[lane[i]]) + s, lg[s, x]) for s in range(window)]
            sampled[i] += out[:, lane[i]].tolist()
        is_argmax = is_argmax and bool(np.array_equal(out[:, lane], np.argmax(lg, axis=-1)))
        d_tok, d_pos = out[-1].astype(np.int32), d_pos + window * d_act.astype(np.int32)
    del k, v, cache
    return rows, sampled, is_argmax


# --- what a step needs ------------------------------------------------------------


def _sizes(cfg: dict) -> dict:
    D, d, r = cfg["hidden_size"], cfg["head_dim"], cfg["router_hidden_size"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    C, choices = q + kv, cfg["num_experts"] + 1
    return {
        "D": D, "L": cfg["num_hidden_layers"], "q": q, "kv": kv, "C": C, "r": r,
        "attn_params": D * (C + kv) + q * D + 2 * C + C + (C // d) * 2 * d * d + C + 4 * D + D,  # w_in, wo, conv0, conv1, merge, norm
        "router_bf16": D * r + 4 * D + D,  # the down-projection, the expert sublayer's merge and norm
        "router_f32": 3 * r + 2 * (r * r + r) + r * choices + choices + cfg["num_key_value_heads"],  # b_d, gamma, norm gain; MLP; beta; (temperatures)
        "router_flops": 2 * (D * r + 2 * r * r + r * choices),
        "expert_params": 3 * D * cfg["moe_intermediate_size"],
        "slot_lanes": 2 * C + kv // 2,
    }


def slot_row_bytes(cfg: dict) -> float:
    """The columns (bf16) of one sequence in ONE layer."""
    return _sizes(cfg)["slot_lanes"] * _bytes_of("bfloat16")


def experts_reached(cfg: dict, rows: float) -> float:
    """Expected number of experts a layer's ``rows`` tokens visit when each
    picks one of the experts and the skip choice uniformly."""
    E = cfg["num_experts"]
    return E * (1.0 - (1.0 - 1.0 / (E + 1)) ** rows)


def decode_step_cost(cfg: dict, weight_dtype: str, rows: float, ctx_tokens: float, experts_visited=None,
                     skipped_rows=None) -> dict:
    """FLOPs and bytes of ONE decode step over ``rows`` sequences whose
    contexts sum to ``ctx_tokens`` tokens. ``experts_visited`` is the number of
    (layer, expert) pairs the step's rows fell on and ``skipped_rows`` the
    (layer, row) pairs that drew the skip choice, both summed over layers (the
    program's step entries carry them); None: the expected numbers under
    uniform routing. ``weight_dtype`` is the compute type: bf16 only."""
    if weight_dtype == "int8":
        raise ValueError("the zaya family counts bf16 weights")
    s, act, f32 = _sizes(cfg), _bytes_of("bfloat16"), _bytes_of("float32")
    D, V, L = s["D"], cfg["vocab_size"], s["L"]
    if experts_visited is None:
        experts_visited = L * experts_reached(cfg, rows)
    if skipped_rows is None:
        skipped_rows = L * rows / (cfg["num_experts"] + 1)
    expert_bytes = experts_visited * s["expert_params"] * act
    mixer_bytes = L * s["attn_params"] * act
    router_bytes = L * (s["router_bf16"] * act + s["router_f32"] * f32)
    head_bytes = (D * V + D) * act  # the tied embedding as the head, and the final norm
    weight_bytes = expert_bytes + mixer_bytes + router_bytes + head_bytes
    slot_bytes = 2.0 * rows * L * slot_row_bytes(cfg)  # read and written
    kv_bytes = L * 2.0 * s["kv"] * act * (ctx_tokens + rows)  # every attended row read, one written a sequence and layer
    io_bytes = rows * (D * act + V * 4.0)  # embedding rows in, float32 logits out
    per_row = L * (2.0 * (D * (s["C"] + s["kv"]) + s["q"] * D + s["C"] * 4 * cfg["head_dim"]) + s["router_flops"]) + 2.0 * D * V
    expert_flops = (L * rows - skipped_rows) * 2.0 * s["expert_params"]
    flops = rows * per_row + expert_flops + L * 4.0 * s["q"] * ctx_tokens
    return {"flops": flops, "bytes": weight_bytes + slot_bytes + kv_bytes + io_bytes,
            "weight_bytes": weight_bytes, "expert_bytes": expert_bytes, "slot_bytes": slot_bytes, "kv_bytes": kv_bytes}
