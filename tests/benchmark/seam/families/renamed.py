"""A second family, added by ``test_benchmark_family_seam.py`` to a copy of the
benchmark as new files only. It reads a configuration file with keys of its
own (``d_model``, ``n_layer``, ...) and counts its decode step from them; the
program serves it through the llama step programs, so the parameter tree and
the walk through those programs are the llama family's; the reference is the
small file beside this one.
"""

from __future__ import annotations

from benchmark.families.llama import make_params, program_logits
from benchmark.families.renamed_reference import CONTROLS, forward as reference_forward

__all__ = ["model_config", "make_params", "program_logits", "reference_forward", "CONTROLS", "decode_step_cost"]


def model_config(cfg: dict, name: str):
    from dynamo_tpu.engine.config import ModelConfig

    eng = cfg["engine"]
    return ModelConfig(
        name=name, vocab_size=cfg["vocab"], hidden_size=cfg["d_model"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], num_kv_heads=cfg["n_kv_head"], head_dim=cfg["d_model"] // cfg["n_head"],
        intermediate_size=cfg["d_ff"], rope_theta=float(cfg["rope_base"]), rms_norm_eps=float(cfg["norm_eps"]),
        max_seq_len=int(min(eng["max_seq_len"], cfg["context"])), dtype=eng["dtype"], weight_dtype=eng["weight_dtype"],
        block_size=int(eng["block_size"]),
    )


def decode_step_cost(cfg: dict, weight_dtype: str, rows: float, ctx_tokens: float) -> dict:
    """Dense layers only, bf16 weights: every weight read once, every context row read, one row written."""
    L, D, F, V = cfg["n_layer"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    kv = 2 * cfg["n_kv_head"] * (D // cfg["n_head"])  # K and V values of one token in one layer
    params = L * (2 * D * D + D * kv + 3 * D * F) + D * V
    return {"flops": 2.0 * rows * params + 4.0 * L * D * ctx_tokens,
            "bytes": 2.0 * (params + L * kv * (ctx_tokens + rows) + rows * D) + 4.0 * rows * V}
