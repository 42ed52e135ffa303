"""Weight-only int8 quantization: serve models ~2× bigger per HBM byte.

The role fp8/int8 weight formats play in the reference's engines
(--quantization levers; GGUF q8_0 is the storage-side equivalent —
llm/gguf.py loads it): layer matmul weights are stored as int8 codes with
a per-output-channel symmetric scale and dequantized to the compute dtype
one LAYER at a time inside the scan, so the resident footprint is the
int8 codes plus one layer's transient bf16 weights. Embedding and
lm_head stay in the compute dtype — re-dequantizing a vocab-sized matrix
every decode step would add ~1 GB of HBM traffic per token at 8B scale.

Measured consequence on a 16 GiB v5e: Llama-3-8B bf16 weights alone are
15.0 GiB and the decode workspace OOMs; with int8 layer weights the
model serves with room for KV.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

# Dense layer matmul weights eligible for int8 storage. MoE expert stacks
# keep their compute dtype (ragged/capacity dispatch paths index them in
# ways that would re-dequantize per expert; revisit if MoE capacity needs
# the headroom).
QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


class QuantW(NamedTuple):
    """int8 weight + per-output-channel scale. A pytree — rides jit args,
    scan xs slices, and donation like a plain array."""

    q: jax.Array  # int8 [..., in, out]
    scale: jax.Array  # f32 [..., 1, out]


def quantize_weight(w: jax.Array) -> QuantW:
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QuantW(q, scale)


def quantize_weight_np(w) -> QuantW:
    """Host-side (numpy) quantization for the checkpoint-load path: the
    bf16 stack never touches the device, so models whose full-precision
    weights exceed HBM (8B on v5e) load straight into int8 residency."""
    import numpy as np

    w32 = np.asarray(w, dtype=np.float32)
    amax = np.max(np.abs(w32), axis=-2, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(w32 / scale), -127, 127).astype(np.int8)
    return QuantW(jnp.asarray(q), jnp.asarray(scale))


def wt(x, dtype=jnp.bfloat16):
    """Dequantize a QuantW to the compute dtype; plain arrays pass through.

    The product runs in f32 (codes are exact in f32, scale is stored f32)
    and only the RESULT casts down: multiplying in bf16 first rounds the
    scale to 8 mantissa bits and compounds a second rounding on the
    product — ~0.4% worst-case extra error per weight, on top of the
    half-code-step quantization floor. XLA still fuses the dequant into
    the consuming matmul's reads either way."""
    if isinstance(x, QuantW):
        return (x.q.astype(jnp.float32) * x.scale).astype(dtype)
    return x


def dequant_layer(lp: Dict, dtype) -> Dict:
    """Per-layer dequant at the top of a layer body: one transient bf16
    copy of this layer's matmul weights (tens of MB), never the stack."""
    if not any(isinstance(v, QuantW) for v in lp.values()):
        return lp
    return {k: wt(v, dtype) for k, v in lp.items()}


def quantize_params(params: Dict) -> Dict:
    """Quantize the dense layer matmul weights of a loaded param tree —
    IN PLACE, one tensor at a time, releasing each bf16 stack before the
    next quantizes. A functional version would hold the full bf16 tree
    and the int8 copies simultaneously: at 8B that is ~23 GiB of HBM and
    OOMs the 16 GiB chip the feature exists to fit (measured). MoE trees
    pass through untouched for non-QUANT_KEYS entries either way."""
    import numpy as np

    layers = params["layers"]
    for k in QUANT_KEYS:
        if k in layers and not isinstance(layers[k], QuantW):
            w = layers.pop(k)
            if w.ndim >= 3:
                # Stacked [L, in, out]: quantize per layer slice — the
                # float32 intermediates of a whole 8B-scale MLP stack are
                # ~2× its bf16 bytes and OOM next to the resident weights.
                qs, ss = [], []
                for l in range(w.shape[0]):
                    qw_l = quantize_weight(w[l])
                    # Sync before the next slice so at most one layer's
                    # float32 intermediates are live at a time.
                    np.asarray(qw_l.scale.ravel()[0:1])
                    qs.append(qw_l.q)
                    ss.append(qw_l.scale)
                qw = QuantW(jnp.stack(qs), jnp.stack(ss))
                np.asarray(qw.scale.ravel()[0:1])
                del qs, ss
            else:
                qw = quantize_weight(w)
                np.asarray(qw.scale.ravel()[0:1])
            del w
            layers[k] = qw
    return params


def params_quantized(params: Dict) -> bool:
    return any(isinstance(v, QuantW) for v in params.get("layers", {}).values())
