"""What any plain reference shares: float32 copies of the weights, the
re-rounding that turns a reference into a control, RMS norm, and the
comparison that decides ``correct``.

A family's forward pass (``benchmark/families/<f>.py`` or the file beside it)
is ``jax.numpy`` in float32 at ``highest`` matmul precision. Weights are taken
one layer (and one expert) at a time so that the float32 copies fit beside the
program's parameters; int8 codes are dequantised as ``codes * scale``. Nothing
here calls the program.

``lower`` turns a reference into the control of "How `correct` is decided":
the same arithmetic with one kind of operand re-rounded to the precision
*below* the one the configuration states. Weights: ``int4`` codes for int8
weights, ``fp8`` (float8_e4m3) or ``int8`` for bfloat16 weights. Activations
(bfloat16 in both configurations): ``fp8_act`` and ``int8_act`` re-round every
matmul's input, one scale per token, as a W8A8 path would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _is_quant(w) -> bool:
    return hasattr(w, "q") and hasattr(w, "scale")


WEIGHT_CONTROLS = ("int4", "fp8", "int8")
ACT_CONTROLS = ("fp8_act", "int8_act")


def _f32(w, lower: str | None):
    """One weight as float32, optionally re-rounded to a lower precision."""
    if lower in ACT_CONTROLS:
        lower = None
    if _is_quant(w):
        q, scale = w.q.astype(F32), w.scale.astype(F32)
        if lower == "int4":
            # int8 codes in [-127, 127] -> int4 codes in [-7, 7] on the same grid.
            q = jnp.clip(jnp.round(q * (7.0 / 127.0)), -7, 7) * (127.0 / 7.0)
        elif lower is not None:
            raise ValueError(f"no control {lower!r} for int8 weights")
        return q * scale
    w32 = w.astype(F32)
    if lower == "fp8":
        # Per-output-channel scale to the float8_e4m3 range, as served fp8 weights are.
        amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (w32 / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    if lower == "int8":
        amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(w32 / scale), -127, 127) * scale
    if lower is not None:
        raise ValueError(f"no control {lower!r} for {w.dtype} weights")
    return w32


def _act(x, act: str | None):
    """A matmul's input, optionally re-rounded per token (the activation controls)."""
    if act is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if act == "fp8_act":
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def rel_err(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """``|got - ref|_2 / |ref|_2`` of each compared position ([N, V] -> [N])."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    assert got.shape == ref.shape, (got.shape, ref.shape)
    per = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    return np.where(np.isfinite(per), per, np.inf)
