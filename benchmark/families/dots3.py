"""The ``dots3`` family: dots3-note-prev language models (multi-head latent
attention behind low-rank queries with a sigmoid gate a head; in the full
layers a learned indexer that picks the cached rows a query attends, in the
sliding layers sizes of their own and a window; a dense first layer, then
sigmoid routing over the experts, of which this chip holds a share, beside one
shared expert), served by ``dynamo_tpu/engine/models/hybrid.py`` through
``ModelConfig.layer_types`` ("mla_full", "mla_window":
``dynamo_tpu/engine/models/latent.py``).

Everything of the benchmark that depends on this architecture: the mapping
from the configuration file's Hugging Face keys, the parameter tree, the
output check's walk through the step programs on the paged pool and the ring
slots beside it, the plain reference (``dots3_reference.py``, beside this
file) and the count of what a decode step needs. The vision tower, the audio
encoder and the multi-token-prediction module the model's card describes have
no sizes in the published ``config.json`` and are not built: the traffic is
text, and ``model_config`` refuses a configuration that states them.

Weights. One layer (for experts: one expert) is drawn at a time inside
``lax.map``, normal at 1/sqrt(fan-in) (0.02 for embedding and head), bf16;
norm gains 1 + 0.1 N(0, 1). What reads a latent that ``mla_lora_rescale`` has
multiplied by ``sqrt(hidden / rank)`` (``W_qb``, the indexer's ``W_Iq``, ``W_uk``,
``W_uv``) is drawn that much smaller, so that a head's queries, keys and values
have unit power and a score spreads about 1: neither a flat softmax nor a
one-hot one. (Drawn at plain 1/sqrt(fan-in) the scores spread 6, every softmax
was near one-hot, and the bfloat16 program read 0.14 against the reference
where the other cells read 0.02, an fp8 step 0.77: my chip run, PR 50, call 1.)
The indexer's key has a LayerNorm gain 1 + 0.1 N and a bias 0.1 N, its head
weights are the projection's. The router at ``ROUTER_SCALE``/sqrt(hidden): its
logits spread 1 and the eight chosen of 256 score 0.9 and up; the correction
bias 0.02 N, float32, small and not zero: where two scores lie within it the
choice and the weight can be told apart.

Output check. The sequences of ``parity.sample_inputs`` go through the
program's own four step programs in the order a scheduler would, on a pool and
ring slots of the sizes the scheduler makes, at the served chunk, decode
bucket and window. The compared sequences are spread down from the highest
slot and block and over the bucket's lanes; the other lanes are idle (tables of
zeros: the scratch block and slot):

1. ``hybrid.prefill`` of the first sequence, every position's logits;
2. each later prompt chunk by chunk. A prompt of three or more chunks goes
   through ``hybrid.prefill`` with every position's logits (its later chunks
   attend the pool's rows through the indexer and the ring as the chunk before
   left it), each chunk followed by one ``hybrid.decode`` step of the
   sequences already admitted; a shorter one through one ``hybrid.mixed_step``
   a chunk, those sequences riding as decode rows. Teacher-forced;
3. ``hybrid.decode_multi`` windows over all sequences (the pool and the rings
   carried through the window's loop).

With ``fault`` no sequence takes a slot (``hybrid.open_slot`` is left out), so
every sequence's window layers read and write the scratch ring together.

Groups: ``prefill`` (the first sequence), ``body`` (positions of a
position-by-position prefill whose context is at most ``index_topk``),
``chosen`` (those past it: the indexer chose), ``rows`` (a length-1 row of a
decode or mixed step, and a mixed step's chunk at its last position) and
``windows``.

Counts. What the algorithm needs: weights as stored (bf16; the router's bias
float32), of the experts only those the step's rows visited; of the cache, for
each row and full layer every index key scored and the rows chosen, one row
and one key written, and for each row and sliding layer the ring read and one
row written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.families.dots3_reference import CONTROLS, forward as reference_forward
from benchmark.parity import pieces
from benchmark.roofline import _bytes_of
from benchmark.weights import seed_key

__all__ = ["model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost"]

ROUTER_SCALE, ROUTER_BIAS = 1.0, 0.02
HEAD, EVERY = 8, 16  # of a position-by-position prefill: the first HEAD positions, every EVERY-th after, the last
KINDS = {"full_attention": "mla_full", "sliding_attention": "mla_window"}
NOT_BUILT = ("vision_config", "audio_config", "num_nextn_predict_layers")


def model_config(cfg: dict, name: str):
    """The program's ``ModelConfig`` from the configuration file's Hugging
    Face keys, as run: the first ``num_hidden_layers`` of the published
    ``layer_types``, ``n_routed_experts`` experts held of the published count
    (``deployment.experts_published``) from ``deployment.first_expert_held``."""
    from dynamo_tpu.engine.config import ModelConfig

    for key in NOT_BUILT:
        if cfg.get(key):
            raise ValueError(f"the dots3 family serves text: {key} (image and audio parts, multi-token prediction) is not built")
    L = cfg["num_hidden_layers"]
    if cfg["model_type"] != "dots3_note" or set(cfg["layer_types"][:L]) - set(KINDS):
        raise ValueError("the dots3 family serves model_type dots3_note with full_attention and sliding_attention layers")
    if (cfg["hidden_act"], cfg["scoring_func"], cfg["topk_method"]) != ("silu", "sigmoid", "noaux_tc") or cfg["attention_bias"]:
        raise ValueError("the dots3 family serves silu experts behind a sigmoid noaux_tc router, no attention bias")
    if cfg["attention_gate_type"] != "headwise" or cfg["swa_attention_gate_type"] != "headwise" or cfg["tie_word_embeddings"]:
        raise ValueError("the dots3 family serves a headwise gate on both kinds and an untied head")
    if cfg["rope_scaling"] is not None or cfg["moe_layer_freq"] != 1 or cfg["n_shared_experts"] != 1:
        raise ValueError("the dots3 family serves no rope scaling, an expert layer in every layer past the dense ones, one shared expert")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"] or cfg["swa_num_key_value_heads"] != cfg["swa_num_attention_heads"]:
        raise ValueError("latent attention has a key and a value a head: num_key_value_heads equals the heads of its kind")
    eng, dep = cfg["engine"], cfg["deployment"]
    return ModelConfig(
        name=name,
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        intermediate_size=cfg["moe_intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(min(eng.get("max_seq_len", cfg["max_position_embeddings"]), cfg["max_position_embeddings"])),
        tie_word_embeddings=False,
        dtype=eng.get("dtype", "bfloat16"),
        block_size=int(eng.get("block_size", 16)),
        num_experts=int(dep["experts_published"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        num_experts_held=int(cfg["n_routed_experts"]),
        first_expert_held=int(dep["first_expert_held"]),
        shared_intermediate_size=int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"]),
        layer_types=tuple(KINDS[k] for k in cfg["layer_types"][:L]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        mla_lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        attention_gate=True,
        index_n_heads=int(cfg["index_n_heads"]),
        index_head_dim=int(cfg["index_head_dim"]),
        index_topk=int(cfg["index_topk"]),
        sliding_window=int(cfg["sliding_window_size"]),
        swa_num_heads=int(cfg["swa_num_attention_heads"]),
        swa_q_lora_rank=int(cfg["swa_q_lora_rank"]),
        swa_kv_lora_rank=int(cfg["swa_kv_lora_rank"]),
        swa_qk_nope_head_dim=int(cfg["swa_qk_nope_head_dim"]),
        swa_qk_rope_head_dim=int(cfg["swa_qk_rope_head_dim"]),
        swa_v_head_dim=int(cfg["swa_v_head_dim"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        router_kind="sigmoid",
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        first_k_dense=int(cfg["first_k_dense_replace"]),
        dense_intermediate_size=int(cfg["intermediate_size"]),
    )


def make_params(mc, seed: int, dtype=None):
    """The parameter tree ``TpuEngine.build(params=...)`` takes for ``mc``
    (``hybrid.init_params``'s layout for the latent kinds: ``mla_full`` and
    ``mla_window`` the mixers of each kind, ``dense`` the first layers' FFN,
    ``layers`` the expert FFN of the others), in the type the configuration
    serves."""
    dtype = jnp.dtype(mc.dtype) if dtype is None else dtype
    D, F, E, Fs, f32 = mc.hidden_size, mc.intermediate_size, mc.experts_held, mc.shared_intermediate_size, jnp.float32

    def mat(key, fan_in, fan_out, scale=1.0):
        return (jax.random.normal(key, (fan_in, fan_out), f32) * (scale * fan_in ** -0.5)).astype(dtype)

    def vec(key, width, spread, mean=0.0, dt=dtype):
        return (mean + spread * jax.random.normal(key, (width,), f32)).astype(dt)

    def swiglu(keys, width, prefix="w_"):
        return {f"{prefix}gate": mat(keys[0], D, width), f"{prefix}up": mat(keys[1], D, width), f"{prefix}down": mat(keys[2], width, D)}

    def mixer(kind):
        z = mc.latent_sizes(kind)
        # What reads a rescaled latent is drawn that much smaller: queries, keys and values of unit power.
        fq, fkv = ((D / z.q_rank) ** -0.5, (D / z.kv_rank) ** -0.5) if mc.mla_lora_rescale else (1.0, 1.0)

        def one(key):
            ks = jax.random.split(key, 16)
            heads = lambda k, a, b, s: (jax.random.normal(k, (z.heads, a, b), f32) * s).astype(dtype)  # noqa: E731
            out = {
                "attn_norm": vec(ks[0], D, 0.1, 1.0), "w_qa": mat(ks[1], D, z.q_rank), "q_norm": vec(ks[2], z.q_rank, 0.1, 1.0),
                "w_qb": mat(ks[3], z.q_rank, z.heads * (z.nope + z.rope), fq), "w_kva": mat(ks[4], D, z.row),
                "kv_norm": vec(ks[5], z.kv_rank, 0.1, 1.0), "w_uk": heads(ks[6], z.nope, z.kv_rank, fkv * z.kv_rank ** -0.5),
                "w_uv": heads(ks[7], z.kv_rank, z.value, fkv * z.kv_rank ** -0.5), "wo": mat(ks[8], z.heads * z.value, D),
                "w_g": mat(ks[9], D, z.heads),
            }
            if kind == "mla_full":
                Hi, di = mc.index_n_heads, mc.index_head_dim
                out.update(wi_q=mat(ks[10], z.q_rank, Hi * di, fq), wi_k=mat(ks[11], D, di), wi_k_gain=vec(ks[12], di, 0.1, 1.0),
                           wi_k_bias=vec(ks[13], di, 0.1), wi_w=mat(ks[14], D, Hi))
            return out

        return one

    def dense_ffn(key):
        ks = jax.random.split(key, 4)
        return {"mlp_norm": vec(ks[0], D, 0.1, 1.0), **swiglu(ks[1:], mc.dense_intermediate_size)}

    def expert_ffn(key):
        ks = jax.random.split(key, 10)
        out = {"mlp_norm": vec(ks[0], D, 0.1, 1.0), "router": mat(ks[1], D, mc.num_experts, ROUTER_SCALE),
               "router_bias": vec(ks[2], mc.num_experts, ROUTER_BIAS, dt=f32), **swiglu(ks[3:6], Fs, "shared_")}
        for name, k, (a, b) in (("w_gate", ks[6], (D, F)), ("w_up", ks[7], (D, F)), ("w_down", ks[8], (F, D))):
            out[name] = lax.map(lambda kk, a=a, b=b: mat(kk, a, b), jax.random.split(k, E))
        return out

    @jax.jit
    def build(key):
        k_embed, k_head, k_norm, k_full, k_win, k_dense, k_ffn = jax.random.split(key, 7)
        n = {kind: mc.layer_types.count(kind) for kind in ("mla_full", "mla_window")}
        out = {
            "embed": (jax.random.normal(k_embed, (mc.vocab_size, D), f32) * 0.02).astype(dtype),
            "lm_head": (jax.random.normal(k_head, (D, mc.vocab_size), f32) * 0.02).astype(dtype),
            "final_norm": vec(k_norm, D, 0.1, 1.0),
        }
        for kind, k in (("mla_full", k_full), ("mla_window", k_win)):
            if n[kind]:
                out[kind] = lax.map(mixer(kind), jax.random.split(k, n[kind]))
        if mc.first_k_dense:
            out["dense"] = lax.map(dense_ffn, jax.random.split(k_dense, mc.first_k_dense))
        if mc.num_layers > mc.first_k_dense:
            out["layers"] = lax.map(expert_ffn, jax.random.split(k_ffn, mc.num_layers - mc.first_k_dense))
        return out

    return build(seed_key(seed))


def _kept(length: int, head: int) -> list:
    """Positions of a position-by-position prefill of ``length`` that are compared."""
    return sorted({*range(min(head, length)), *range(head, length, EVERY), length - 1})


def table_width(mc, longest: int) -> int:
    """The table of the check's sequences: the scheduler's rung that holds ``longest`` rows."""
    from dynamo_tpu.engine.scheduler import width_bucket

    return width_bucket(-(-longest // mc.block_size), -(-mc.max_seq_len // mc.block_size))


def program_logits(params, mc, spec: dict, lens, prompts, forced, fault: bool = False):
    """Runs the programs. Returns ``(rows, sampled, sampled_is_argmax)``:
    ``rows`` is a list of ``(group, sequence, position, logits [V])`` and
    ``sampled[i]`` the ids the windows fed back for sequence ``i``. With
    ``fault`` no sequence takes a slot: all share the scratch ring."""
    from dynamo_tpu.engine.kv_cache import KvCacheArrays
    from dynamo_tpu.engine.models import hybrid

    cfg = mc
    chunk, window, windows, batch = (int(spec[k]) for k in ("chunk", "window", "windows", "decode_bucket"))
    num_blocks, slots = int(spec["num_blocks"]), int(spec["max_running"]) + 1  # the pool and the slots the scheduler makes
    n = len(lens)
    W = table_width(cfg, max(lens[i] + len(forced[i]) for i in range(n)) + windows * window + 1)
    if n > batch or batch > slots - 1 or 1 + n * W > num_blocks:
        raise ValueError("the compared sequences do not fit the stated bucket, pool and slots")
    lane = [(2 * i + 1) * batch // (2 * n) for i in range(n)]
    slot = [slots - 1 - i * ((slots - 2) // n) for i in range(n)]
    tables = np.zeros((batch, W), np.int32)
    for i in range(n):
        tables[lane[i]] = num_blocks - (i + 1) * W + np.arange(W)
    cache = KvCacheArrays.create(cfg, num_blocks, dtype=params["embed"].dtype, num_slots=slots)  # as the engine makes them
    # A prompt is prefilled under the widest table, as the scheduler prefills it (``Scheduler._prompt_width``).
    wide = lambda i: np.pad(tables[lane[i]], (0, -(-cfg.max_seq_len // cfg.block_size) - W))  # noqa: E731
    kept = HEAD + chunk // EVERY + 1

    prefill = jax.jit(
        lambda p, k, v, t, vl, cl, bt, keep: (lambda lg, k, v, _: (lg[keep], k, v))(*hybrid.prefill(
            p, cfg, k, v, t, vl, cl, bt, all_logits=True)), donate_argnums=(1, 2))
    mixed = jax.jit(lambda p, k, v, *a: hybrid.mixed_step(p, cfg, k, v, *a)[:3], donate_argnums=(1, 2))
    decode = jax.jit(lambda p, k, v, *a: hybrid.decode(p, cfg, k, v, *a)[:3], donate_argnums=(1, 2))
    multi = jax.jit(lambda p, k, v, *a: hybrid.decode_multi(p, cfg, k, v, *a, window, return_logits=True)[:4], donate_argnums=(1, 2))
    open_slot = jax.jit(hybrid.open_slot, donate_argnums=(0, 1))

    def piece(k, v, tokens, start, table, keep):
        """One chunk through ``hybrid.prefill``; the logits of its positions ``keep``."""
        toks, idx = np.zeros((chunk,), np.int32), np.zeros((kept,), np.int32)
        toks[: len(tokens)], idx[: len(keep)] = tokens, keep
        lg, k, v = prefill(params, k, v, jnp.asarray(toks), jnp.int32(len(tokens)), jnp.int32(start), jnp.asarray(table), jnp.asarray(idx))
        return np.asarray(lg)[: len(keep)], k, v

    k, v = cache.k, cache.v
    if not fault:
        for i in range(n):
            k, v = open_slot(k, v, jnp.int32(tables[lane[i]][0]), jnp.int32(slot[i]))
    d_tok, d_pos, d_act = np.zeros((batch,), np.int32), np.zeros((batch,), np.int32), np.zeros((batch,), bool)

    keep = _kept(lens[0], HEAD)
    lg, k, v = piece(k, v, prompts[0], 0, wide(0), keep)
    rows = [("prefill", 0, t, lg[x]) for x, t in enumerate(keep)]
    fed = [0] * n  # forced tokens each sequence has consumed

    def ride(j):
        """The sequences admitted before ``j`` take their next forced token as decode rows."""
        for i in range(j):
            d_tok[lane[i]], d_pos[lane[i]], d_act[lane[i]] = forced[i][fed[i]], lens[i] + fed[i], True
        return jnp.asarray(np.where(d_act[:, None], tables, 0))  # a row not yet admitted: a table of zeros, the scratch slot

    def rode(j, lg):
        for i in range(j):
            rows.append(("rows", i, int(d_pos[lane[i]]), lg[lane[i]]))
            fed[i] += 1

    for j in range(1, n):
        by_position = len(pieces(lens[j], chunk)) >= 3
        for start, length in pieces(lens[j], chunk):
            if by_position:
                keep = _kept(length, 2)
                lg, k, v = piece(k, v, prompts[j][start:start + length], start, wide(j), keep)
                rows += [("chosen" if start + t >= cfg.index_topk else "body", j, start + t, lg[x]) for x, t in enumerate(keep)]
                d_tab = ride(j)
                lg, k, v = decode(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), d_tab, jnp.asarray(d_act))
                rode(j, np.asarray(lg))
                continue
            toks = np.zeros((chunk,), np.int32)
            toks[:length] = prompts[j][start:start + length]
            d_tab = ride(j)
            lg, k, v = mixed(params, k, v, jnp.asarray(toks), jnp.int32(length), jnp.int32(start), jnp.asarray(wide(j)),
                             jnp.asarray(d_tok), jnp.asarray(d_pos), d_tab, jnp.asarray(d_act))
            lg = np.asarray(lg)
            rows.append(("rows", j, start + length - 1, lg[0]))
            rode(j, lg[1:])

    for i in range(n):
        d_tok[lane[i]], d_pos[lane[i]], d_act[lane[i]] = forced[i][fed[i]], lens[i] + fed[i], True
    sampled = [[] for _ in range(n)]
    is_argmax = True
    greedy = (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32), jnp.ones((batch,), jnp.float32))
    d_tab = jnp.asarray(np.where(d_act[:, None], tables, 0))
    for _ in range(windows):
        out, lg, k, v = multi(params, k, v, jnp.asarray(d_tok), jnp.asarray(d_pos), d_tab, jnp.asarray(d_act), *greedy,
                              jax.random.PRNGKey(0))
        out, lg = np.asarray(out), np.asarray(lg[:, np.asarray(lane)])
        for x, i in enumerate(range(n)):
            rows += [("windows", i, int(d_pos[lane[i]]) + s, lg[s, x]) for s in range(window)]
            sampled[i] += out[:, lane[i]].tolist()
        is_argmax = is_argmax and bool(np.array_equal(out[:, lane], np.argmax(lg, axis=-1)))
        d_tok, d_pos = out[-1].astype(np.int32), d_pos + window * d_act.astype(np.int32)
    del k, v, cache
    return rows, sampled, is_argmax


# --- what a step needs ------------------------------------------------------------


def _mixer_params(cfg: dict, swa: bool) -> dict:
    """Parameters of one attention sublayer, by part."""
    D, p = cfg["hidden_size"], "swa_" if swa else ""
    H = cfg["swa_num_attention_heads" if swa else "num_attention_heads"]
    dn, dr, dv = cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"], cfg[p + "v_head_dim"]
    rq, rkv = cfg[p + "q_lora_rank"], cfg[p + "kv_lora_rank"]
    out = {"q": D * rq + rq + rq * H * (dn + dr), "kv": D * (rkv + dr) + rkv + rkv * H * (dn + dv), "o": H * dv * D,
           "gate": D * H, "norm": D, "indexer": 0}
    if not swa:
        Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        out["indexer"] = rq * Hi * di + D * di + 2 * di + D * Hi
    return out


def _sizes(cfg: dict) -> dict:
    D, L, F = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["moe_intermediate_size"]
    kinds = cfg["layer_types"][:L]
    dense = cfg["first_k_dense_replace"]
    return {
        "D": D, "L": L, "full": kinds.count("full_attention"), "window": kinds.count("sliding_attention"), "dense": dense,
        "expert_layers": L - dense, "full_params": sum(_mixer_params(cfg, False).values()),
        "window_params": sum(_mixer_params(cfg, True).values()), "dense_params": 3 * D * cfg["intermediate_size"] + D,
        "expert_params": 3 * D * F, "shared_params": 3 * D * F * cfg["n_shared_experts"],
        "router_params": D * cfg["deployment"]["experts_published"] + D,  # the router and the sublayer's norm, bf16
        "row_full": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], "row_window": cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"],
    }


def parameter_count(cfg: dict) -> int:
    """Parameters the configuration holds as run (the correction bias counted: float32 in memory)."""
    s = _sizes(cfg)
    per_expert_layer = cfg["n_routed_experts"] * s["expert_params"] + s["shared_params"] + s["router_params"] \
        + cfg["deployment"]["experts_published"]
    return (s["full"] * s["full_params"] + s["window"] * s["window_params"] + s["dense"] * s["dense_params"]
            + s["expert_layers"] * per_expert_layer + 2 * cfg["vocab_size"] * s["D"] + s["D"])


def experts_reached(cfg: dict, rows: float) -> float:
    """Expected number of held experts a layer's ``rows`` tokens visit under uniform routing."""
    E, K = cfg["deployment"]["experts_published"], cfg["num_experts_per_tok"]
    return cfg["n_routed_experts"] * (1.0 - (1.0 - K / E) ** rows)


def decode_step_cost(cfg: dict, weight_dtype: str, rows: float, ctx_tokens: float, experts_visited=None,
                     held_assignments=None, indexed_rows=None, index_ctx=None) -> dict:
    """FLOPs and bytes of ONE decode step over ``rows`` sequences whose
    contexts sum to ``ctx_tokens`` tokens. ``experts_visited`` is the number of
    (layer, held expert) pairs the step's rows fell on, ``held_assignments`` the
    (layer, row, choice) triples that fell on held experts, ``indexed_rows``
    the cached rows the full layers' queries chose and ``index_ctx`` the rows
    their indexers scored, all summed over layers (the program's step entries
    carry them); None: the expected numbers. ``weight_dtype`` is the compute
    type: bf16 only."""
    if weight_dtype == "int8":
        raise ValueError("the dots3 family counts bf16 weights")
    s, act = _sizes(cfg), _bytes_of("bfloat16")
    D, V = s["D"], cfg["vocab_size"]
    Le, K, E = s["expert_layers"], cfg["num_experts_per_tok"], cfg["deployment"]["experts_published"]
    W, Hi, di = cfg["sliding_window_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    if experts_visited is None:
        experts_visited = Le * experts_reached(cfg, rows)
    if held_assignments is None:
        held_assignments = Le * rows * K * cfg["n_routed_experts"] / E
    if index_ctx is None:
        index_ctx = s["full"] * (ctx_tokens + rows)
    if indexed_rows is None:
        indexed_rows = s["full"] * rows * min(cfg["index_topk"], (ctx_tokens + rows) / max(rows, 1))
    expert_bytes = experts_visited * s["expert_params"] * act
    rest_bytes = (s["full"] * s["full_params"] + s["window"] * s["window_params"] + s["dense"] * s["dense_params"]
                  + Le * (s["shared_params"] + s["router_params"]) + D * V + D) * act + Le * E * 4.0
    index_bytes = index_ctx * di * act + s["full"] * rows * di * act  # every key scored, one written a row and full layer
    chosen_bytes = indexed_rows * s["row_full"] * act + s["full"] * rows * s["row_full"] * act
    ring_rows = min(W, ctx_tokens / max(rows, 1) + 1.0)
    ring_bytes = s["window"] * rows * (ring_rows + 1.0) * s["row_window"] * act  # the ring read, one row written
    io_bytes = rows * (D * act + V * 4.0)
    f, w = _mixer_heads(cfg, False), _mixer_heads(cfg, True)
    per_row = 2.0 * (s["full"] * f["proj"] + s["window"] * w["proj"] + s["dense"] * (s["dense_params"] - D)
                     + Le * (s["shared_params"] + D * E) + D * V)
    attn_flops = 2.0 * (indexed_rows * f["per_key"] + s["window"] * rows * ring_rows * w["per_key"]) + 2.0 * index_ctx * Hi * di
    flops = rows * per_row + held_assignments * 2.0 * s["expert_params"] + attn_flops
    weight_bytes = expert_bytes + rest_bytes
    return {"flops": flops, "bytes": weight_bytes + index_bytes + chosen_bytes + ring_bytes + io_bytes,
            "weight_bytes": weight_bytes, "expert_bytes": expert_bytes, "index_bytes": index_bytes,
            "chosen_bytes": chosen_bytes, "ring_bytes": ring_bytes, "kv_bytes": index_bytes + chosen_bytes + ring_bytes}


def _mixer_heads(cfg: dict, swa: bool) -> dict:
    """Multiply-adds of one attention sublayer: a row's projections with the
    absorbed products (``proj``) and one attended row (``per_key``: the score
    over latent and rotated lanes, the weighted latent)."""
    p = "swa_" if swa else ""
    D, H = cfg["hidden_size"], cfg["swa_num_attention_heads" if swa else "num_attention_heads"]
    dn, dr, dv = cfg[p + "qk_nope_head_dim"], cfg[p + "qk_rope_head_dim"], cfg[p + "v_head_dim"]
    rq, rkv = cfg[p + "q_lora_rank"], cfg[p + "kv_lora_rank"]
    proj = D * rq + rq * H * (dn + dr) + D * (rkv + dr) + H * dn * rkv + H * rkv * dv + H * dv * D + D * H
    if not swa:
        proj += rq * cfg["index_n_heads"] * cfg["index_head_dim"] + D * cfg["index_head_dim"] + D * cfg["index_n_heads"]
    return {"proj": proj, "per_key": H * (2 * rkv + dr)}
