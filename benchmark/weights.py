"""What every family's weights share: a key from ``--seed`` and weight-only int8.

A family makes its parameter tree on the device in one jitted call from the
seed, in the type it is served in (``benchmark/families/<f>.py::make_params``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
