"""Parity tests for the Pallas paged flash-decode kernel (interpret mode)
against the XLA gather reference — same (m, l, acc) partial contract.

The kernel is explicit opt-in (attention_impl="paged"); these tests keep it
correct while it waits for a runtime where per-pallas-call dispatch cost
does not dominate (see ModelConfig.attention_impl)."""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.attention.decode import paged_decode_partials
from dynamo_tpu.engine.models.llama import _attend_piece, _merge_pieces


def _reference(q, kp, vp, tables, lengths, KVH):
    B, H, HD = q.shape
    G = H // KVH
    BS = kp.shape[1]
    ctx = tables.shape[1] * BS
    k_ctx = kp[tables].reshape(B, ctx, KVH, HD)
    v_ctx = vp[tables].reshape(B, ctx, KVH, HD)
    mask = jnp.arange(ctx)[None, :] < lengths[:, None]
    qg = q.reshape(B, KVH, G, HD)
    return _attend_piece(qg, k_ctx, v_ctx, mask, HD**-0.5)


def test_kernel_matches_gather_partials():
    B, BS, KVH, HD, G = 4, 32, 2, 64, 4
    H = KVH * G
    NP_, W = 40, 6
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (NP_, BS, KVH * HD), jnp.float32)  # pages as the pool stores them
    vp = kp * 0.5 + 1
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, HD), jnp.float32)
    tables = jnp.array(
        [[3, 7, 11, 0, 0, 0], [5, 6, 0, 0, 0, 0], [9, 4, 8, 2, 12, 13], [0, 0, 0, 0, 0, 0]],
        jnp.int32,
    )
    lengths = jnp.array([70, 33, 192, 0], jnp.int32)

    m, l, acc = paged_decode_partials(
        q, kp, vp, tables, lengths, num_kv_heads=KVH, block_size=BS, interpret=True
    )
    m2, l2, acc2 = _reference(q, kp, vp, tables, lengths, KVH)

    # Rows 0-2 carry real prefixes — partials must match. Row 3 is empty:
    # the kernel returns the canonical empty piece (m=-inf, l=0) while the
    # gather reference returns (m=-1e30, l=ctx); both vanish in the merge.
    np.testing.assert_allclose(np.asarray(m[:3]), np.asarray(m2[:3]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l[:3]), np.asarray(l2[:3]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(acc[:3]), np.asarray(acc2[:3]), rtol=1e-4, atol=1e-4)
    assert float(jnp.max(l[3])) == 0.0


def test_empty_piece_drops_out_of_merge():
    B, BS, KVH, HD, G = 2, 16, 2, 32, 2
    H = KVH * G
    key = jax.random.PRNGKey(2)
    kp = jax.random.normal(key, (8, BS, KVH * HD), jnp.float32)
    vp = kp + 1
    q = jax.random.normal(jax.random.PRNGKey(3), (B, H, HD), jnp.float32)
    tables = jnp.zeros((B, 4), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)  # all rows empty
    m1, l1, acc1 = paged_decode_partials(
        q, kp, vp, tables, lengths, num_kv_heads=KVH, block_size=BS, interpret=True
    )
    # Merge the empty kernel piece with a one-token in-register piece: the
    # result must equal attention over that single token alone.
    qg = q.reshape(B, KVH, G, HD)
    k1t = jax.random.normal(jax.random.PRNGKey(4), (B, 1, KVH, HD), jnp.float32)
    v1t = k1t * 2
    m2, l2, acc2 = _attend_piece(qg, k1t, v1t, jnp.ones((B, 1), bool), HD**-0.5)
    out = _merge_pieces(m1, l1, acc1, m2, l2, acc2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v1t[:, 0, :, None, :].repeat(G, 2) * 0 + v1t[:, 0][:, :, None, :]), rtol=1e-5)
