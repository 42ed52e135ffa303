"""The table of peaks and the functions that count what a decode step needs.

The counts are the work the algorithm needs, not what a program happens to
move: weights as stored (int8 codes plus float32 scales for W8, bf16
otherwise), the bf16 head, the embedding rows read, the KV rows of the
contexts actually in the step and the rows it writes; for sparse experts only
the experts the step's tokens are expected to reach. An unknown device is an
error, never a default.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} (have {sorted(table)})")
    return table[device_kind]


def _bytes_of(dtype: str) -> float:
    return {"bfloat16": 2.0, "float16": 2.0, "float32": 4.0, "int8": 1.0}[dtype]


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


def min_seconds(cost: dict, device_kind: str) -> dict:
    """The least time the chip could take for ``cost`` and which peak bounds it."""
    p = peaks(device_kind)
    t_c, t_m = cost["flops"] / p["bf16_flops_per_s"], cost["bytes"] / p["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c > t_m else "memory"}
