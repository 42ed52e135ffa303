"""Weights from ``--seed``, made on the device in one jitted call, in the type
they are served in.

The program's own ``init_params`` draws every stack in float32 on the default
device before anything is quantised: Mistral-7B needs 14.5 GB in bf16 alone
and does not fit a 16 GB chip that way. Here one layer (for experts: one
expert) is drawn at a time inside ``lax.map``, scaled like ``init_params``
(normal, 1/sqrt(fan-in); 0.02 for the embedding and the head), and for
``weight_dtype == "int8"`` turned into int8 codes with one float32 scale per
output channel (symmetric, amax/127) before the next is drawn. The program and
the float32 reference are both handed this tree; the reference dequantises the
same codes. Only the container type ``QuantW`` is the program's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for any whole ``seed`` (the driver's exceed 31 bits). The
    ``rbg`` generator: the chip draws 7e9 normals in seconds with it."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def quantize(w: jax.Array):
    """Weight-only int8, one symmetric scale per output channel."""
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale


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
