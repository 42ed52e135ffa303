"""Layer-group step programs: a stack whose layers are not all alike.

``ModelConfig.layer_types`` names each layer's mixer, "attention" (GQA over
the paged pool), "mamba" (a Mamba-2 state-space mixer over one *slot* of
recurrent state a sequence) or "cca" (attention in a compressed latent over
the paged pool, behind two causal convolutions and a value shift whose last
columns a sequence keeps in its slot: pool AND slot in every layer); every
layer has the same FFN (sparse experts of
which this chip may hold a share, a shared expert beside them, or a dense
SwiGLU; the experts behind a linear router or the ZAYA router MLP, which
hands a state from layer to layer). The stack is driven as the ordered groups of one kind that
``layer_types`` spells (``ModelConfig.layer_groups``): each group is one
``lax.scan`` whose body indexes the kind's own stacked weights and the FFN
stacks of all layers, so the compiled program holds one body a group, never
one a layer, and the expert GEMMs read the one ``[L*E_held, D, F]`` view
across all groups in place (``llama._split_expert_stacks``).

The four step programs the scheduler serves — ``prefill``, ``mixed_step``,
``decode``, ``decode_multi`` — take and return what ``llama``'s do, with the
cache sides as ``kv_cache.SlotKv`` (the attention layers' pool, and beside it
the slot arrays: recurrent state on the ``k`` side, the convolution's last
columns on the ``v`` side) and one more result at the end: the expert layer's
counts for the step log (``held_assignments``, ``experts_visited``, summed
over layers). A row's slot is ``slot_of[table[0]]`` (``open_slot``): padded
rows and tables of zeros read and write scratch slot 0.

The Mamba-2 mixer has two bodies over one set of weights: the single-step
recurrence for length-1 rows (decode rows; a multi-step window carries the
slot arrays in its loop; on a TPU the Pallas kernel ``ssm_update_rows``, which
advances the rows' slots in place, elsewhere a gather, ``_ssm_update`` and a
scatter) and the chunked (SSD) form of the same recurrence for a wide row (a
prefill chunk), which takes the slot's state and columns in and writes them
back. A slot's state is stored ``[H/g, N, g*P]`` (heads side by side on the
lanes, ``ModelConfig.mamba_state_shape``), which both bodies read as it lies.
Padded positions of a chunk get a step of zero length (decay 1, no input) and
are left out of the new columns, so they leave the slot as it was.

Attention layers go through the llama family's attention paths ("gather",
"megakernel"; "paged" is refused), without rope where the configuration says
so; a stated ``attention_scale`` is folded into the queries, so that no kernel
needs to know it. The Granite multipliers scale the embedding rows, every
residual branch and the logits.

The cca mixer (``_cca_mixer``) has one body for both forms: a length-1 row
takes what stood before it out of its slot, a chunk's rows take it from the
row before and the chunk's first from the slot, so chunks of a prompt agree
with one pass over it; padded positions leave the slot as it was. The keys are
cached as attended (normed, tempered, rotated), so the pool's rows and every
attention path are those of a GQA layer of ``num_heads``/``num_kv_heads``
heads. The ZAYA router's state is a second carry of the layer scan beside
``h``; it is float32 from the down-projection on.

The latent kinds "mla_full" and "mla_window" (``latent.py`` has their mixers
and says what each caches) go through ``_drive_latent``: a stack of them alone
(``ModelConfig.is_latent``), its runs those of one kind AND one FFN
(``ModelConfig.latent_groups``: the first ``first_k_dense`` layers have a dense
SwiGLU, the others the expert FFN behind ``_sigmoid_route``). A full layer
writes the step's rows into the pool before it attends, so the pools ride the
layer scans and a multi-step window's loop with the rings, written in place;
the counts gain ``indexed_rows`` and ``index_ctx`` (``LATENT_AUX_KEYS``).

Two drivers, and why they stay two. ``_drive`` hands its mixers an ``attend``
closure over a pool that no layer of the step writes (the step's new rows
come back from the scan and ``_write`` scatters them once, after the stack; a
window keeps them in a small carry): the pool is an operand of the scan and is
never copied. A latent layer's index keys and latent row must be IN the pool
before the layer attends (a query may choose its own row, and a chunk's rows
choose among the chunk's), so ``_drive_latent`` carries the pool through every
scan and the pool's layer axis is the scan's. Giving ``_drive`` that carry would
make every attention and cca layer of the other families scan over the whole
pool for nothing. What the two share is shared by calls: the expert FFN
(``_ffn``), embedding and head, and the multi-step window (``_window``:
sampling, the window's tokens and logits, the counts' sums) around each
driver's one step. Each driver names its counts beside it (``AUX_KEYS``,
``LATENT_AUX_KEYS``); ``_window`` takes the names from its caller.

Not built for this kind, and refused by name
(``ModelConfig.refuse_for_layer_types``): prefix-block reuse,
KV export/injection and the KVBM tiers, speculative verification and rollback,
wave admission, a mesh, sequence embeddings, int8 weights.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.engine.kv_cache import SlotKv, layer_flat, ragged_scatter_targets
from dynamo_tpu.engine.models import latent, llama
from dynamo_tpu.engine.models.llama import (  # noqa: F401 — the scheduler reads the resolvers off its model module
    Params,
    _attend_piece,
    _gather_kv,
    _mega_attend_rows,
    _mega_rows_work,
    _merge_pieces,
    _moe_held,
    _norm,
    _scatter_kv,
    _split_expert_stacks,
    _use_megakernel,
    chunk_attn_path,
    chunk_walks_tiles,
    decode_targets,
    resolve_attention_impl,
    resolve_prefill_impl,
    rows_pages_per_step,
    warn_attention_impl_degrade,
)

_HI = lax.Precision.HIGHEST  # the chunked scan's small float32 products: never a bf16 pass


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init weights (testing). ``layers`` holds the FFN of every layer,
    ``attn``, ``mamba`` and ``cca`` the mixers of the layers of each kind,
    stacked in layer order. A cca mixer's ``w_in`` is its four projections
    side by side, ``[Wq | Wk | Wv1 | Wv2]``; ``conv1_w[g]`` is head ``g``'s two
    taps stacked, the earlier token's on top. The ZAYA router's tensors past
    its down-projection, and the keys' temperatures, are float32."""
    c = config
    if c.is_latent:
        return _init_latent(c, key, dtype)
    D, L, La, Lm = c.hidden_size, c.num_layers, c.num_attention_layers, c.num_mamba_layers
    ks = iter(jax.random.split(key, 24))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return (jax.random.normal(next(ks), shape, dtype=jnp.float32) * scale).astype(dtype)

    def norm(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, dtype=jnp.float32)).astype(dtype)

    layers: Dict[str, jax.Array] = {"mlp_norm": norm((L, D))}
    F = c.intermediate_size
    # (The tensors of the kinds added later draw from a stream of their own: the older presets' weights stay what they were.)
    ks2 = iter(jax.random.split(jax.random.fold_in(key, 1), 40))

    def f32(shape, scale, mean=0.0):
        return mean + scale * jax.random.normal(next(ks2), shape, dtype=jnp.float32)

    def merge(n):
        return {"res_gx": f32((n, D), 0.1, 1.0).astype(dtype), "res_bx": f32((n, D), 0.02).astype(dtype),
                "res_gf": f32((n, D), 0.1, 1.0).astype(dtype), "res_bf": f32((n, D), 0.02).astype(dtype)}

    if c.num_experts:
        E = c.experts_held
        if c.router_kind != "zaya":
            layers.update(router=dense((L, D, c.num_experts)))
        layers.update(w_gate=dense((L, E, D, F)), w_up=dense((L, E, D, F)), w_down=dense((L, E, F, D)))
        if c.router_kind == "zaya":
            r, n = c.router_hidden_size, c.router_choices
            layers.update(
                router_down=f32((L, D, r), D ** -0.5).astype(dtype), router_down_b=f32((L, r), 0.1),
                router_gamma=f32((L, r), 0.2, 1.0), router_norm=f32((L, r), 0.1, 1.0),
                router_w1=f32((L, r, r), 2.0 * r ** -0.5), router_b1=f32((L, r), 0.1),
                router_w2=f32((L, r, r), 2.0 * r ** -0.5), router_b2=f32((L, r), 0.1),
                router_w3=f32((L, r, n), 4.0 * r ** -0.5), router_beta=f32((L, n), 0.02),
            )
    else:
        layers.update(w_gate=dense((L, D, F)), w_up=dense((L, D, F)), w_down=dense((L, F, D)))
    if c.residual_merge:
        layers.update(merge(L))
    if c.shared_intermediate_size:
        Fs = c.shared_intermediate_size
        layers.update(shared_gate=dense((L, D, Fs)), shared_up=dense((L, D, Fs)), shared_down=dense((L, Fs, D)))
    params: Params = {"embed": dense((c.vocab_size, D), scale=0.02), "final_norm": norm((D,)), "layers": layers}
    Lc = c.num_cca_layers
    if Lc:
        C, hd = c.cca_channels, c.head_dim
        params["cca"] = {
            "attn_norm": f32((Lc, D), 0.1, 1.0).astype(dtype),
            "w_in": f32((Lc, D, C + c.kv_size), D ** -0.5).astype(dtype), "wo": f32((Lc, c.q_size, D), c.q_size ** -0.5).astype(dtype),
            "conv0_w": f32((Lc, 2, C), 2 ** -0.5).astype(dtype), "conv0_b": f32((Lc, C), 0.1).astype(dtype),
            "conv1_w": f32((Lc, C // hd, 2 * hd, hd), (2 * hd) ** -0.5).astype(dtype), "conv1_b": f32((Lc, C), 0.1).astype(dtype),
            "k_temp": 6.0 + 4.0 * jax.random.uniform(next(ks2), (Lc, c.num_kv_heads), dtype=jnp.float32),
            **(merge(Lc) if c.residual_merge else {}),
        }
    else:
        params["attn"] = {
            "attn_norm": norm((La, D)),
            "wq": dense((La, D, c.q_size)), "wk": dense((La, D, c.kv_size)),
            "wv": dense((La, D, c.kv_size)), "wo": dense((La, c.q_size, D)),
        }
    if Lm:
        H, di, cd, K = c.mamba_n_heads, c.mamba_d_inner, c.mamba_conv_dim, c.mamba_d_conv
        # Steps log-uniform in [1e-3, 1e-1] and A in [1, 16], as Mamba-2 is initialised: per-step decays
        # exp(-dt * A) from 0.2 to 0.999.
        dt = jnp.exp(jax.random.uniform(next(ks), (Lm, H), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        params["mamba"] = {
            "norm": norm((Lm, D)),
            "in_proj": dense((Lm, D, 2 * di + 2 * c.mamba_n_groups * c.mamba_d_state + H)),
            "conv_w": dense((Lm, K, cd), scale=K ** -0.5), "conv_b": dense((Lm, cd), scale=0.1),
            "dt_bias": jnp.log(jnp.expm1(dt)).astype(jnp.float32),  # softplus^-1
            "A_log": jnp.log(jax.random.uniform(next(ks), (Lm, H), minval=1.0, maxval=16.0)),
            "D": jnp.ones((Lm, H), jnp.float32),
            "gate_norm": norm((Lm, di)),
            "out_proj": dense((Lm, di, D)),
        }
    if not c.tie_word_embeddings:
        params["lm_head"] = dense((D, c.vocab_size), scale=0.02)
    return params


def _init_latent(c: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights (testing) of a stack of latent layers: ``mla_full`` and
    ``mla_window`` the mixers of each kind (``latent.init_mixer``), ``dense``
    the FFN of the first ``first_k_dense`` layers, ``layers`` the expert FFN of
    the others (``router_bias``, the stored correction, float32)."""
    D, Ld, F, E = c.hidden_size, c.first_k_dense, c.intermediate_size, c.experts_held
    Le = c.num_layers - Ld
    ks = iter(jax.random.split(key, 24))

    def dense(shape, scale=None):
        scale = shape[-2] ** -0.5 if scale is None else scale
        return (jax.random.normal(next(ks), shape, dtype=jnp.float32) * scale).astype(dtype)

    def gain(shape):
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape, dtype=jnp.float32)).astype(dtype)

    params: Params = {"embed": dense((c.vocab_size, D), 0.02), "final_norm": gain((D,))}
    for kind in latent.LATENT_KINDS:
        if kind in c.layer_types:
            params[kind] = latent.init_mixer(c, kind, c.layer_types.count(kind), next(ks), dtype)
    if Ld:
        Fd = c.dense_intermediate_size
        params["dense"] = {"mlp_norm": gain((Ld, D)), "w_gate": dense((Ld, D, Fd)), "w_up": dense((Ld, D, Fd)),
                           "w_down": dense((Ld, Fd, D))}
    if Le:
        layers = {"mlp_norm": gain((Le, D)), "router": dense((Le, D, c.num_experts), 4.0 * D ** -0.5),
                  "router_bias": 0.02 * jax.random.normal(next(ks), (Le, c.num_experts), dtype=jnp.float32),
                  "w_gate": dense((Le, E, D, F)), "w_up": dense((Le, E, D, F)), "w_down": dense((Le, E, F, D))}
        if c.shared_intermediate_size:
            Fs = c.shared_intermediate_size
            layers.update(shared_gate=dense((Le, D, Fs)), shared_up=dense((Le, D, Fs)), shared_down=dense((Le, Fs, D)))
        params["layers"] = layers
    if not c.tie_word_embeddings:
        params["lm_head"] = dense((D, c.vocab_size), 0.02)
    return params


def open_slot(k_cache: SlotKv, v_cache: SlotKv, block: jax.Array, slot: jax.Array) -> Tuple[SlotKv, SlotKv]:
    """A sequence whose table begins with ``block`` takes ``slot``: its state
    and columns are zeroed in every layer and the step programs find it
    through ``slot_of``. (Donate both sides: three in-place writes; a stack of
    cca layers keeps no state, two.)"""
    state = k_cache.slots.at[:, slot].set(0.0) if k_cache.slots.size else k_cache.slots
    return (
        k_cache._replace(slots=state, slot_of=k_cache.slot_of.at[block].set(slot)),
        v_cache._replace(slots=v_cache.slots.at[:, slot].set(0)),
    )


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _at(tree, i):
    """Layer ``i`` of stacked weights (``i`` traced: what a scan's ``xs`` does)."""
    return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False), tree)


def _embed(c: ModelConfig, params: Params, tokens: jax.Array):
    h = params["embed"].at[tokens].get(mode="clip")
    return h * jnp.asarray(c.embedding_multiplier, h.dtype), h.dtype


def _logits(c: ModelConfig, params: Params, h: jax.Array, wdtype) -> jax.Array:
    x = _norm(c, h, params["final_norm"], wdtype)
    head = params.get("lm_head")
    head = head if head is not None else params["embed"].T
    return (x @ head).astype(jnp.float32) / c.logits_scaling


def _qkv(c: ModelConfig, lp, x: jax.Array, positions: jax.Array):
    """Queries (with the stated scale folded in, so every attention path's own
    ``head_dim ** -0.5`` makes it up), keys and values of ``x``'s rows."""
    R = x.shape[0]
    rotate = positions if c.use_rope else None
    q = llama.project_heads(x, lp["wq"], c.num_heads, rotate, c.rope_theta)
    k = llama.project_heads(x, lp["wk"], c.num_kv_heads, rotate, c.rope_theta)
    v = (x @ lp["wv"]).reshape(R, c.num_kv_heads, c.head_dim)
    if c.attention_scale:
        q = (q.astype(jnp.float32) * (c.attention_scale * c.head_dim ** 0.5)).astype(q.dtype)
    return q, k, v


def _rows_attention(c: ModelConfig, k_pool, v_pool, tables, prefix_lens, active, window: int = 0, step=None):
    """Attention of ``B`` length-1 rows (``active`` ``[B]`` bool: the live
    ones) over their paged prefixes, as ``llama.decode_layer_scan``
    (``window`` 0) and its window variant have it: returns ``attend(q, k, v,
    la, kwl=None, vwl=None) -> [B, q_size]`` for attention layer ``la``;
    ``kwl``/``vwl`` ``[w, B, KVH, HD]`` are the rows the window wrote before
    step ``step``."""
    B, N, bs = tables.shape[0], k_pool.shape[1], c.block_size
    kvh, G, hd = c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim
    ctx, w = tables.shape[1] * bs, window
    k_flat, v_flat = layer_flat(k_pool), layer_flat(v_pool)
    use_mega = _use_megakernel(c, k_pool)
    prefix_lens = jnp.minimum(prefix_lens, ctx).astype(jnp.int32)
    if use_mega:
        from dynamo_tpu.engine.attention.megakernel import build_meta

        rows_i = jnp.arange(B, dtype=jnp.int32)
        first = rows_i * (w + 1)
        meta = build_meta(rows_i, prefix_lens, first, first + 1 + (0 if step is None else step), active)
        work = _mega_rows_work(c, k_pool, prefix_lens, active, tables.shape[1])
    else:
        mask = jnp.arange(ctx, dtype=jnp.int32)[None, :] < prefix_lens[:, None]
        small_mask = jnp.ones((B, 1), dtype=bool)
        if w:
            small_mask = jnp.concatenate(
                [jnp.broadcast_to((jnp.arange(w, dtype=jnp.int32) < step)[None, :], (B, w)), small_mask], axis=1
            )

    def attend(q, k, v, la, kwl=None, vwl=None):
        tables_l = tables + la * N
        if use_mega:
            if w:
                k = jnp.concatenate([k[:, None], jnp.swapaxes(kwl, 0, 1)], axis=1).reshape(B * (w + 1), kvh, hd)
                v = jnp.concatenate([v[:, None], jnp.swapaxes(vwl, 0, 1)], axis=1).reshape(B * (w + 1), kvh, hd)
            return _mega_attend_rows(c, q, k, v, k_flat, v_flat, tables_l, meta, work).astype(q.dtype).reshape(B, c.q_size)
        qg = q.reshape(B, kvh, G, hd)
        k_ctx = _gather_kv(k_flat, tables_l, q.dtype).reshape(B, ctx, kvh, hd)
        v_ctx = _gather_kv(v_flat, tables_l, q.dtype).reshape(B, ctx, kvh, hd)
        m1, l1, acc1 = _attend_piece(qg, k_ctx, v_ctx, mask, hd ** -0.5)
        k_small, v_small = k[:, None], v[:, None]
        if w:
            k_small = jnp.concatenate([jnp.swapaxes(kwl, 0, 1), k_small], axis=1)
            v_small = jnp.concatenate([jnp.swapaxes(vwl, 0, 1), v_small], axis=1)
        m2, l2, acc2 = _attend_piece(qg, k_small, v_small, small_mask, hd ** -0.5)
        return _merge_pieces(m1, l1, acc1, m2, l2, acc2).astype(q.dtype).reshape(B, c.q_size)

    return attend


def _chunk_attention(c: ModelConfig, k_pool, v_pool, table, prefix_rows, valid_len, T: int, use_flash, has_prefix):
    """Attention of one wide row (a prefill chunk of ``T`` queries) over
    ``[its paged prefix ; itself]``, as ``llama.prefill`` has it: returns
    ``attend(q, k, v, la) -> [T, q_size]``."""
    N, bs, kvh, hd = k_pool.shape[1], c.block_size, c.num_kv_heads, c.head_dim
    ctx = table.shape[0] * bs
    k_flat, v_flat = layer_flat(k_pool), layer_flat(v_pool)
    walks_tiles = chunk_walks_tiles(c, k_pool)
    if walks_tiles:
        from dynamo_tpu.engine.attention.megakernel import build_meta

        t_iq = jnp.arange(T, dtype=jnp.int32)
        meta = build_meta(
            jnp.zeros((T,), jnp.int32), jnp.full((T,), prefix_rows, jnp.int32),
            jnp.zeros((T,), jnp.int32), t_iq + 1, (t_iq < valid_len).astype(jnp.int32),
        )

    def attend(q, k, v, la):
        table_l = table + la * N
        if walks_tiles:
            out = _mega_attend_rows(c, q, k, v, k_flat, v_flat, table_l[None, :], meta)
            return out.astype(q.dtype).reshape(T, c.q_size)
        from dynamo_tpu.engine.attention.ragged import ragged_chunk_attention

        k_ctx = v_ctx = None
        if not (use_flash and not has_prefix):
            k_ctx = _gather_kv(k_flat, table_l, q.dtype).reshape(ctx, kvh, hd)
            v_ctx = _gather_kv(v_flat, table_l, q.dtype).reshape(ctx, kvh, hd)
        out = ragged_chunk_attention(
            q, k, v, k_ctx, v_ctx, valid_len, prefix_rows,
            num_kv_heads=kvh, use_flash=use_flash, has_prefix=has_prefix, interpret=not llama._on_tpu(),
        )
        return out.reshape(T, c.q_size)

    return attend


# --- the Mamba-2 mixer -------------------------------------------------------


def _mamba_split(c: ModelConfig, zxbcdt: jax.Array):
    """``in_proj``'s columns: gate ``z``, the convolution's lanes ``xBC``, ``dt``."""
    di, cd = c.mamba_d_inner, c.mamba_conv_dim
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def _ssm_inputs(c: ModelConfig, lp, xbc: jax.Array, dt: jax.Array):
    """The recurrence's float32 inputs of ``R`` rows from the convolved lanes:
    ``x [R, H, P]``, ``B``/``C`` per head ``[R, H, N]`` (a group's heads share
    them), steps ``dt [R, H]`` and ``A [H]`` (negative)."""
    R, H, P, G, N = xbc.shape[0], c.mamba_n_heads, c.mamba_d_head, c.mamba_n_groups, c.mamba_d_state
    di = c.mamba_d_inner
    x = xbc[:, :di].reshape(R, H, P)
    Bh = jnp.repeat(xbc[:, di:di + G * N].reshape(R, G, N), H // G, axis=1)
    Ch = jnp.repeat(xbc[:, di + G * N:].reshape(R, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    return x, Bh, Ch, dt, -jnp.exp(lp["A_log"].astype(jnp.float32))


def _ssm_update(state, x, Bh, Ch, dt, A, D):
    """One step of the recurrence for ``B`` rows (float32, elementwise and a
    lane reduction: one pass over the state): ``h <- exp(dt A) h + dt x (x) B``,
    ``y = h C + D x``. Returns ``(y [B, H, P], state [B, H, P, N])``."""
    with jax.named_scope("ssm_update"):
        state = state * jnp.exp(dt * A)[..., None, None] + (dt[..., None] * x)[..., None] * Bh[:, :, None, :]
        y = jnp.sum(state * Ch[:, :, None, :], axis=-1) + D[None, :, None] * x
    return y, state


def _from_slot(c: ModelConfig, stored: jax.Array) -> jax.Array:
    """A slot's state as the recurrence writes it, ``[..., H, P, N]``, from
    how it is stored (``ModelConfig.mamba_state_shape``)."""
    Hg, N, gP = c.mamba_state_shape
    lead, g = stored.shape[:-3], c.mamba_n_heads // Hg
    h = stored.reshape(*lead, Hg, N, g, gP // g)
    return jnp.moveaxis(h, -3, -1).reshape(*lead, c.mamba_n_heads, gP // g, N)


def _to_slot(c: ModelConfig, state: jax.Array) -> jax.Array:
    """The inverse of ``_from_slot``."""
    Hg, N, gP = c.mamba_state_shape
    lead, g = state.shape[:-3], c.mamba_n_heads // Hg
    h = state.reshape(*lead, Hg, g, gP // g, N)
    return jnp.moveaxis(h, -1, -3).reshape(*lead, Hg, N, gP)


def _rows_kernel_fits(c: ModelConfig) -> bool:
    """``ssm_update_rows`` wants one group, whole lanes (heads side by side
    fill 128) and ``d_state`` in whole sublanes."""
    Hg, N, gP = c.mamba_state_shape
    return c.mamba_n_groups == 1 and gP == 128 and N % 8 == 0 and (Hg % 8 == 0 or Hg < 8)


def _use_rows_kernel(c: ModelConfig) -> bool:
    """On a TPU (llama's ``_on_tpu``: the one place that decides) where the
    kernel fits; elsewhere a gather, ``_ssm_update`` and a scatter."""
    return llama._on_tpu() and _rows_kernel_fits(c)


def _ssm_rows_kernel(rows_ref, ssm_ref, dx_ref, dec_ref, b_ref, c_ref, ssm_out_ref, y_ref, *, tiles: int):
    """One (row, block of lane rows) cell of ``ssm_update_rows``: each of the
    block's ``[N, 128]`` tiles (``g`` heads side by side) read, advanced and
    written back. ``dx`` and the decay are lane rows that broadcast down the
    sublanes, ``B`` and ``C`` come broadcast along the lanes, and ``y`` is a
    sum down the sublanes: a lane row again. Nothing is transposed."""
    del rows_ref  # read by the block specs' index maps
    Bb, Cb = b_ref[0], c_ref[0]  # [N, 128]
    for i in range(tiles):
        s = ssm_ref[0, i] * dec_ref[0, i:i + 1, :] + dx_ref[0, i:i + 1, :] * Bb
        ssm_out_ref[0, i] = s
        y_ref[0, i:i + 1, :] = jnp.sum(s * Cb, axis=0, keepdims=True)


def ssm_update_rows(c: ModelConfig, ssm, rows, x, Bm, Cm, dt, A, D, *, interpret: bool = False, tiles: int = 16):
    """``_ssm_update`` for ``B`` rows IN PLACE on the slot array (a Pallas
    kernel): ``ssm [R, *mamba_state_shape]`` float32 is aliased to the first
    result, and row ``b`` reads and writes slot ``rows[b]`` (scalar prefetch:
    the block specs fetch the slot, nothing is gathered or scattered), once.
    One group: ``Bm``/``Cm`` are ``[B, N]``. Rows that name the same slot
    (padded rows, the scratch slot) overwrite each other; consecutive ones
    fetch it once. Returns ``(ssm, y [B, H, P])``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, P = x.shape
    Hg, N, gP = c.mamba_state_shape
    hb = min(tiles, Hg)
    lanes = lambda a: jnp.broadcast_to(a[:, :, None], (B, H, P)).reshape(B, Hg, gP)  # noqa: E731 - a head's value on its lanes
    dx, dec = (dt[:, :, None] * x).reshape(B, Hg, gP), lanes(jnp.exp(dt * A))
    down = lambda a: jnp.broadcast_to(a[:, :, None], (B, N, gP))  # noqa: E731 - B and C along the lanes
    slot = pl.BlockSpec((1, hb, N, gP), lambda b, j, rows: (rows[b], j, 0, 0))
    row = pl.BlockSpec((1, hb, gP), lambda b, j, rows: (b, j, 0))
    whole = pl.BlockSpec((1, N, gP), lambda b, j, rows: (b, 0, 0))
    with jax.named_scope("ssm_update"):
        ssm, y = pl.pallas_call(
            functools.partial(_ssm_rows_kernel, tiles=hb),
            out_shape=(jax.ShapeDtypeStruct(ssm.shape, ssm.dtype), jax.ShapeDtypeStruct((B, Hg, gP), jnp.float32)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, Hg // hb),
                in_specs=[slot, row, row, whole, whole], out_specs=(slot, row),
            ),
            input_output_aliases={1: 0},  # the slot array (operand 1, after the prefetched rows) is result 0
            interpret=interpret,
            name="ssm_update_rows",
        )(rows.astype(jnp.int32), ssm, dx, dec, down(Bm), down(Cm))
        return ssm, y.reshape(B, H, P) + D[None, :, None] * x


def _ssd_chunk(state, x, Bh, Ch, dt, A, D, block: int):
    """The chunked (SSD) form of the same recurrence over ``T`` positions of
    one sequence, ``block`` positions at a time, from and to the slot's state
    AS STORED (``[H/g, N, g*P]``, ``ModelConfig.mamba_state_shape``: the two
    products that touch the state take it as it lies, so nothing of the slot
    array is transposed): inside a block every position reads the state the
    block began with, decayed, and the earlier positions of the block through
    the masked matrix of their decays; the state then moves to the block's
    end. A position with ``dt`` 0 neither decays nor feeds the state. Returns
    ``(y [T, H, P], state)``."""
    T, H, P = x.shape
    Hg, N, gP = state.shape
    g, Q = H // Hg, min(block, T)
    if T % Q:
        raise ValueError(f"a chunk of {T} positions is not whole blocks of {Q}")
    causal = jnp.tril(jnp.ones((Q, Q), dtype=bool))
    pairs = lambda a: a.reshape(a.shape[0], Hg, g, *a.shape[2:])  # noqa: E731 - [.., H, ..] as lane rows of g heads

    def one(state, xs):
        x, Bh, Ch, dt = xs  # [Q, H, ...]
        cs = jnp.cumsum(dt * A, axis=0)  # [Q, H] log-decay from the block's start through t
        decay = jnp.exp(jnp.where(causal[:, :, None], cs[:, None, :] - cs[None, :, :], -jnp.inf))  # [t, s, H]
        scores = jnp.einsum("thn,shn->tsh", Ch, Bh, precision=_HI) * decay * dt[None, :, :]
        y = jnp.einsum("tsh,shp->thp", scores, x, precision=_HI)
        from_state = jnp.einsum("tagn,angp->tagp", pairs(Ch), state, precision=_HI).reshape(Q, H, P)
        y = y + from_state * jnp.exp(cs)[:, :, None]
        to_end = jnp.exp(cs[-1][None, :] - cs) * dt  # [s, H]
        fed = jnp.einsum("sagp,sagn->angp", pairs(to_end[:, :, None] * x), pairs(Bh), precision=_HI)
        state = state * jnp.exp(cs[-1]).reshape(Hg, 1, g, 1) + fed
        return state, y + D[None, :, None] * x

    with jax.named_scope("ssd_chunk"):
        blocks = jax.tree.map(lambda a: a.reshape(T // Q, Q, *a.shape[1:]), (x, Bh, Ch, dt))
        state, y = lax.scan(one, state.reshape(Hg, N, g, P), blocks)
    return y.reshape(T, H, P), state.reshape(Hg, N, gP)


def _conv_chunk(lp, cols, xbc, valid_len):
    """Causal depthwise convolution (with bias) and silu over a chunk's rows
    ``xbc [T, C]`` after the slot's columns ``cols [K-1, C]``. Returns
    (float32 ``[T, C]``, the new columns: the last ``K-1`` *valid* inputs)."""
    with jax.named_scope("ssm_conv"):
        K, T = lp["conv_w"].shape[0], xbc.shape[0]
        seq = jnp.concatenate([cols.astype(xbc.dtype), xbc], axis=0)  # [K-1+T, C]; input t stands at row K-1+t
        w = lp["conv_w"].astype(jnp.float32)
        out = sum(seq[k:k + T].astype(jnp.float32) * w[k] for k in range(K)) + lp["conv_b"].astype(jnp.float32)
        return jax.nn.silu(out), lax.dynamic_slice_in_dim(seq, valid_len, K - 1, axis=0)


def _conv_rows(lp, cols, xbc):
    """The same convolution for ``B`` length-1 rows: ``cols [B, K-1, C]``."""
    with jax.named_scope("ssm_conv"):
        window = jnp.concatenate([cols.astype(xbc.dtype), xbc[:, None]], axis=1)  # [B, K, C]
        out = jnp.sum(window.astype(jnp.float32) * lp["conv_w"].astype(jnp.float32)[None], axis=1)
        return jax.nn.silu(out + lp["conv_b"].astype(jnp.float32)), window[:, 1:]


def _mamba_out(c: ModelConfig, lp, y: jax.Array, z: jax.Array, wdtype) -> jax.Array:
    """Gate, then norm over all inner lanes, then ``out_proj``."""
    y = y.reshape(y.shape[0], c.mamba_d_inner) * jax.nn.silu(z.astype(jnp.float32))
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + c.rms_norm_eps) * lp["gate_norm"].astype(jnp.float32)
    return y.astype(wdtype) @ lp["out_proj"]


def _mamba_mixer(c: ModelConfig, lp, lm, x, ssm, conv, slots, chunk, wdtype):
    """The Mamba-2 mixer of Mamba layer ``lm`` over ``x``'s rows: first the
    wide row ``chunk = (T, slot, valid_len)`` if any (the chunked form, on
    that slot), then length-1 rows on ``slots [B]`` (the single step). One
    ``in_proj`` and one ``out_proj`` over all rows. ``ssm``/``conv`` are the
    slot arrays viewed ``[L_m*S, ...]`` (the state as stored:
    ``ModelConfig.mamba_state_shape``); returns ``(out [R, D], ssm, conv)``."""
    S = ssm.shape[0] // c.num_mamba_layers
    z, xbc, dt = _mamba_split(c, x @ lp["in_proj"])
    D = lp["D"].astype(jnp.float32)
    ys = []
    T = 0
    if chunk is not None:
        T, slot, valid_len = chunk
        row = lm * S + slot
        act, cols = _conv_chunk(lp, lax.dynamic_index_in_dim(conv, row, keepdims=False), xbc[:T], valid_len)
        xs, Bh, Ch, dts, A = _ssm_inputs(c, lp, act, dt[:T])
        dts = jnp.where((jnp.arange(T, dtype=jnp.int32) < valid_len)[:, None], dts, 0.0)
        # (The state goes in and out as stored: a transposed view of the slice made XLA:TPU re-lay the WHOLE slot array,
        # 2.45 GB a layer at the benchmark's sizes; compiled for a described v5e, PR 32.)
        y, state = _ssd_chunk(lax.dynamic_index_in_dim(ssm, row, keepdims=False), xs, Bh, Ch, dts, A, D, c.mamba_chunk_size)
        ssm = lax.dynamic_update_index_in_dim(ssm, state, row, axis=0)
        conv = lax.dynamic_update_index_in_dim(conv, cols.astype(conv.dtype), row, axis=0)
        ys.append(y)
        if slots is None and _use_rows_kernel(c):
            # A chunk with no decode row beside it: one idle step on the layer's scratch slot all the same. The kernel's
            # operand pins the slot array's layout; left to itself XLA:TPU re-lays the whole array around the chunk's
            # products (2.5 GB of temporaries and two copies a dispatch; compiled for a described v5e, PR 32).
            H, P, N = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
            idle = jnp.zeros((1, H), jnp.float32)
            ssm, _ = ssm_update_rows(c, ssm, jnp.reshape(lm * S, (1,)), jnp.zeros((1, H, P), jnp.float32),
                                     jnp.zeros((1, N), jnp.float32), jnp.zeros((1, N), jnp.float32), idle, A, D,
                                     interpret=not llama._on_tpu())
    if slots is not None:
        rows = lm * S + slots
        act, cols = _conv_rows(lp, conv[rows], xbc[T:])
        xs, Bh, Ch, dts, A = _ssm_inputs(c, lp, act, dt[T:])
        if _use_rows_kernel(c):
            ssm, y = ssm_update_rows(c, ssm, rows, xs, Bh[:, 0], Ch[:, 0], dts, A, D, interpret=not llama._on_tpu())
        else:
            y, state = _ssm_update(_from_slot(c, ssm[rows]), xs, Bh, Ch, dts, A, D)
            ssm = ssm.at[rows].set(_to_slot(c, state))
        conv = conv.at[rows].set(cols.astype(conv.dtype))
        ys.append(y)
    return _mamba_out(c, lp, jnp.concatenate(ys) if len(ys) > 1 else ys[0], z, wdtype), ssm, conv


# --- the cca mixer -----------------------------------------------------------


_wide = latent.wide  # einsum with a float32 result of compute-dtype operands


def _unit(x: jax.Array) -> jax.Array:
    """``x / |x|_2`` over the last axis (float32; a zero row stays zero)."""
    return x * lax.rsqrt(jnp.maximum(jnp.sum(x * x, axis=-1, keepdims=True), 1e-30))


def _rope_part(c: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    """Rotate the leading ``rope_fraction`` of each head's lanes of ``x [R, heads, head_dim]``."""
    rd = int(c.head_dim * c.rope_fraction)
    rotated = llama.apply_rope(x[..., :rd], positions, c.rope_theta)
    return rotated if rd == c.head_dim else jnp.concatenate([rotated, x[..., rd:]], axis=-1)


def _cca_mixer(c: ModelConfig, lp, lc, x, cols, slots, chunk, positions, attend, win=()):
    """The cca mixer of cca layer ``lc`` over ``x``'s rows: first the wide row
    ``chunk = (T, slot, valid_len)`` if any, then length-1 rows on ``slots
    [B]``. One ``w_in``, one attention launch a kind of row (``attend``) and
    one ``wo`` over all rows. ``cols`` is the slot array viewed ``[L_c*S,
    cca_slot_lanes]``: a slot holds, side by side, the last token's ``[q~ ;
    k~]`` (the first convolution's earlier input), its first convolution's
    output (the second's earlier input) and its projection for the shifted
    value heads. A length-1 row reads what stood before it out of its slot; a
    chunk's row reads the row before it, the first the slot; the slot then
    takes the chunk's last *valid* row. Returns ``(out [R, q_size], cols,
    k [R, KVH, HD], v)``, the keys as cached: unit norm times the head's
    temperature, rotated, with ``sqrt(head_dim)`` folded in so that every
    attention path's own ``head_dim ** -0.5`` makes it up."""
    R, C, Q, hd = x.shape[0], c.cca_channels, c.q_size, c.head_dim
    Hq, Hk, half = c.num_heads, c.num_kv_heads, c.kv_size // 2
    S = cols.shape[0] // c.num_cca_layers
    with jax.named_scope("cca_conv"):
        proj = x @ lp["w_in"]  # [R, C + kv_size]: q~ | k~ | this token's value heads | the next token's shifted ones
        qk, v_own, v_next = proj[:, :C], proj[:, C:C + half], proj[:, C + half:]
        T = 0
        if chunk is not None:
            T, slot, valid_len = chunk
            row = lc * S + slot
            old_chunk = lax.dynamic_index_in_dim(cols, row, keepdims=True)  # [1, W]: the slot as the chunk finds it
        if slots is not None:
            rows = lc * S + slots
            old_rows = cols[rows].astype(x.dtype)  # [B, W]

        def earlier(cur, lo, hi):
            """What stood before each row of ``cur``, the slot's lanes ``[lo, hi)``."""
            parts = [old_chunk[:, lo:hi].astype(x.dtype), cur[:T - 1]] if chunk is not None else []
            return jnp.concatenate(parts + ([old_rows[:, lo:hi]] if slots is not None else []))

        w0 = lp["conv0_w"].astype(jnp.float32)
        y = (earlier(qk, 0, C).astype(jnp.float32) * w0[0] + qk.astype(jnp.float32) * w0[1]
             + lp["conv0_b"].astype(jnp.float32)).astype(x.dtype)  # depthwise, two taps
        taps = jnp.concatenate([earlier(y, C, 2 * C).reshape(R, C // hd, hd), y.reshape(R, C // hd, hd)], axis=-1)
        z = _wide("rgk,gko->rgo", taps, lp["conv1_w"])  # grouped by head, two taps
        z = z + lp["conv1_b"].astype(jnp.float32).reshape(C // hd, hd)
        # The q-k mean goes round the convolutions: each query head with its key head, each key head with its queries' mean.
        qt = qk[:, :Q].astype(jnp.float32).reshape(R, Hk, Hq // Hk, hd)
        kt = qk[:, Q:].astype(jnp.float32).reshape(R, Hk, 1, hd)
        q = z[:, :Hq] + (0.5 * (qt + kt)).reshape(R, Hq, hd)
        k = z[:, Hq:] + 0.5 * (jnp.mean(qt, axis=2) + kt[:, :, 0])
        temp = lp["k_temp"].astype(jnp.float32) * hd ** 0.5
        q = _rope_part(c, _unit(q), positions).astype(x.dtype)
        k = _rope_part(c, _unit(k) * temp[None, :, None], positions).astype(x.dtype)
        v = jnp.concatenate([v_own, earlier(v_next, 2 * C, 2 * C + half)], axis=1).reshape(R, Hk, hd)
        now = jnp.concatenate([qk, y, v_next], axis=1).astype(cols.dtype)  # [R, W]: what each row leaves behind
        if chunk is not None:
            # Row ``valid_len`` of [the slot as it was ; the chunk's rows]: the last valid row, or with none the slot itself.
            left = lax.dynamic_index_in_dim(jnp.concatenate([old_chunk, now[:T]]), valid_len, keepdims=False)
            cols = lax.dynamic_update_index_in_dim(cols, left, row, axis=0)
        if slots is not None:
            cols = cols.at[rows].set(now[T:])
    return attend(q, k, v, lc, *win) @ lp["wo"], cols, k, v


def _zaya_route(c: ModelConfig, lp, x: jax.Array, s: jax.Array):
    """The ZAYA router on the expert layer's input ``x [R, D]`` (the normed
    stream, float32: not yet rounded to the compute type) and the state ``s [R,
    router_hidden_size]`` the layer before handed on (zeros before the first):
    ``r = x W_d + b_d + gamma * s`` goes on to the next layer; ``p =
    softmax(MLP(norm(r)))`` over the experts and the skip choice; the choice is
    the argmax of ``p + beta`` and its weight ``p`` there. Float32 throughout,
    at full precision: with one expert a token, a tie that rounding turns sends
    the whole token elsewhere, and the rounding of the router's input to
    bfloat16 alone turned as many choices as all the rest of a bfloat16 stream
    (PERF.md section 6, PR 41). Returns ``(weights [R, 1], ids [R, 1], r)``."""
    with jax.named_scope("zaya_router"):
        r = jnp.dot(x, lp["router_down"].astype(jnp.float32), precision=_HI) + lp["router_down_b"] + lp["router_gamma"] * s
        a = r * lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + c.rms_norm_eps) * lp["router_norm"]
        a = jax.nn.gelu(jnp.dot(a, lp["router_w1"], precision=_HI) + lp["router_b1"], approximate=False)
        a = jax.nn.gelu(jnp.dot(a, lp["router_w2"], precision=_HI) + lp["router_b2"], approximate=False)
        p = jax.nn.softmax(jnp.dot(a, lp["router_w3"], precision=_HI), axis=-1)
        ids = jnp.argmax(p + lp["router_beta"], axis=-1).astype(jnp.int32)[:, None]
        return jnp.take_along_axis(p, ids, axis=-1), ids, r


def _sigmoid_route(c: ModelConfig, x: jax.Array, lp):
    """The sigmoid router on the expert layer's input ``x [R, D]``: scores
    ``sigmoid(x W_r)`` in float32 at full precision (a top-k boundary turns on
    rounding), the choice the top-k of score + the stored correction bias, the
    weights the chosen scores (summing to 1 where ``norm_topk_prob``) times
    ``routed_scaling_factor``. Returns ``(weights [R, K], ids [R, K])``."""
    with jax.named_scope("sigmoid_router"):
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), lp["router"].astype(jnp.float32), precision=_HI))
        _, ids = lax.top_k(s + lp["router_bias"], c.num_experts_per_tok)
        w = jnp.take_along_axis(s, ids, axis=-1)
        if c.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w * c.routed_scaling_factor, ids.astype(jnp.int32)


# --- the stack ---------------------------------------------------------------


def _residual(c: ModelConfig, lp, h: jax.Array, out: jax.Array) -> jax.Array:
    """A sublayer's output joins the stream: scaled by the residual multiplier,
    or merged with the sublayer's four learned vectors."""
    if not c.residual_merge:
        return h + out * jnp.asarray(c.residual_multiplier, out.dtype)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    merged = (f32(lp["res_gx"]) * f32(h) + f32(lp["res_bx"])) + (f32(lp["res_gf"]) * f32(out) + f32(lp["res_bf"]))
    return merged.astype(h.dtype)


def _stats0(c: ModelConfig, rows: int) -> tuple:  # (AUX_KEYS names its counts)
    """What the layer scans carry beside ``h`` and the slot arrays: the expert
    layer's counts ``(held, visited)``, and with the ZAYA router the rows that
    drew the skip and the router's state ``[rows, router_hidden_size]``."""
    counts = (jnp.int32(0), jnp.int32(0))
    if c.router_kind != "zaya":
        return counts
    return counts + (jnp.int32(0), jnp.zeros((rows, c.router_hidden_size), jnp.float32))


AUX_KEYS = ("held_assignments", "experts_visited", "skipped_rows")  # the step log's names of ``_stats0``'s counts, in its order


def _ffn(c: ModelConfig, scanned, experts, h, l, valid, wdtype, stats):
    """The FFN every layer has, as a residual branch: the held experts' share
    and the shared expert. Returns ``(h, stats)`` (``_stats0``)."""
    lp = _at(scanned, l)
    x = _norm(c, h, lp["mlp_norm"], wdtype)
    counts, state = (jnp.int32(0), jnp.int32(0)), ()  # this layer's counts; the router's state for the next
    if c.num_experts:
        route = None
        if c.router_kind == "zaya":
            weights, ids, r = _zaya_route(c, lp, _norm(c, h, lp["mlp_norm"], jnp.float32), stats[3])
            route = lambda *_: (weights, ids)  # noqa: E731 - routed already: the state had to come out
        elif c.router_kind == "sigmoid":
            route = functools.partial(_sigmoid_route, c)
        out, held, visited = _moe_held(x, lp, c, valid, experts, l, route=route)
        counts = (held, visited)
        if c.router_kind == "zaya":
            counts, state = counts + (jnp.sum(valid & (ids[:, 0] == c.num_experts)).astype(jnp.int32),), (r,)
    else:
        out = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
    if c.shared_intermediate_size:
        with jax.named_scope("moe_shared"):
            out = out + (jax.nn.silu(x @ lp["shared_gate"]) * (x @ lp["shared_up"])) @ lp["shared_down"]
    h = _residual(c, lp, h, out)
    return h, tuple(total + n for total, n in zip(stats, counts)) + state


def _drive(c: ModelConfig, params: Params, h, ssm, conv, attend, positions, slots, chunk, valid, wdtype, window=None):
    """``h`` through the stack, group by group (``ModelConfig.layer_groups``),
    each group one scan over its layers' indices. ``attend(q, k, v, la, ...)``
    is the step's attention over the pool; ``slots``/``chunk`` say which rows
    are length-1 rows and which a wide row (``_mamba_mixer``, ``_cca_mixer``);
    ``window`` ``(k_win, v_win) [L_a, w, B, KVH, HD]`` are a multi-step window's
    rows so far. Returns ``(h, ssm, conv, k_rows, v_rows, aux)``: the attention
    and cca layers' fresh rows ``[L_a, R, KVH, HD]`` for the caller's one
    scatter, and the expert counts."""
    scanned, experts = _split_expert_stacks(c, params["layers"])
    l0 = la0 = lm0 = 0
    k_rows, v_rows = [], []
    stats = _stats0(c, h.shape[0])
    flat = jax.tree.map(lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), (ssm, conv))

    def mamba_layer(carry, idx):
        h, (ssm, conv), stats = carry
        l, lm = idx
        lp = _at(params["mamba"], lm)
        out, ssm, conv = _mamba_mixer(c, lp, lm, _norm(c, h, lp["norm"], wdtype), ssm, conv, slots, chunk, wdtype)
        h, stats = _ffn(c, scanned, experts, _residual(c, lp, h, out), l, valid, wdtype, stats)
        return (h, (ssm, conv), stats), None

    def attention_layer(carry, idx):
        h, flat, stats = carry
        l, la = idx
        lp = _at(params["attn"], la)
        q, k, v = _qkv(c, lp, _norm(c, h, lp["attn_norm"], wdtype), positions)
        win = () if window is None else tuple(lax.dynamic_index_in_dim(a, la, keepdims=False) for a in window)
        out = attend(q, k, v, la, *win) @ lp["wo"]
        h, stats = _ffn(c, scanned, experts, _residual(c, lp, h, out), l, valid, wdtype, stats)
        return (h, flat, stats), (k, v)

    def cca_layer(carry, idx):
        h, (none, cols), stats = carry
        l, lc = idx
        lp = _at(params["cca"], lc)
        win = () if window is None else tuple(lax.dynamic_index_in_dim(a, lc, keepdims=False) for a in window)
        out, cols, k, v = _cca_mixer(c, lp, lc, _norm(c, h, lp["attn_norm"], wdtype), cols, slots, chunk, positions, attend, win)
        h, stats = _ffn(c, scanned, experts, _residual(c, lp, h, out), l, valid, wdtype, stats)
        return (h, (none, cols), stats), (k, v)

    for kind, count in c.layer_groups:
        layer_ids = jnp.arange(l0, l0 + count, dtype=jnp.int32)
        if kind == "mamba":
            (h, flat, stats), _ = lax.scan(mamba_layer, (h, flat, stats), (layer_ids, layer_ids - l0 + lm0))
            lm0 += count
        else:
            body = cca_layer if kind == "cca" else attention_layer
            (h, flat, stats), (k, v) = lax.scan(body, (h, flat, stats), (layer_ids, layer_ids - l0 + la0))
            k_rows.append(k)
            v_rows.append(v)
            la0 += count
        l0 += count
    ssm, conv = (a.reshape(b.shape) for a, b in zip(flat, (ssm, conv)))
    return h, ssm, conv, jnp.concatenate(k_rows), jnp.concatenate(v_rows), dict(zip(AUX_KEYS, stats[:3]))


# --- the latent kinds' stack ---------------------------------------------------

LATENT_AUX_KEYS = ("held_assignments", "experts_visited", "indexed_rows", "index_ctx")  # the step log's names of a latent stack's counts


def _drive_latent(c: ModelConfig, params: Params, h, k_cache: SlotKv, v_cache: SlotKv, positions, blocks, offs, rows, chunk,
                  valid, wdtype):
    """``h`` through a stack of latent layers (``latent.py``), run by run
    (``ModelConfig.latent_groups``: one kind, one FFN), each run one scan.
    ``blocks``/``offs`` ``[R]`` are where every row's pool rows go (a full
    layer writes them before it attends); ``chunk = (T, table [W], valid_len)``
    is the wide row, if any, and ``rows = (tables [B, W], active [B])`` the
    length-1 rows after it. The pools and the rings are carried through the
    scans and written in place. Returns ``(h, k_cache, v_cache, aux)``:
    ``aux`` the expert layer's counts and, of the length-1 rows' full layers,
    the rows chosen and the rows scored."""
    Ld = c.first_k_dense
    scanned, experts = _split_expert_stacks(c, params["layers"]) if c.num_layers > Ld else (None, None)
    k_flat, i_flat = layer_flat(k_cache.pool), layer_flat(v_cache.pool)
    rings = layer_flat(v_cache.slots)
    N, S = k_cache.pool.shape[1], v_cache.slots.shape[1]
    T = chunk[0] if chunk is not None else 0
    if chunk is not None:
        chunk_slot = _slots_of(k_cache, chunk[1])
    if rows is not None:
        row_slots = jnp.where(rows[1], _slots_of(k_cache, rows[0]), 0)

    def ffn(dense: bool, h, l, stats):
        if not dense:
            return _ffn(c, scanned, experts, h, l - Ld, valid, wdtype, stats)
        lp = _at(params["dense"], l)
        x = _norm(c, h, lp["mlp_norm"], wdtype)
        with jax.named_scope("dense_ffn"):
            return h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"], stats

    def layer(kind: str, dense: bool):
        z, full = c.latent_sizes(kind), kind == "mla_full"

        def body(carry, idx):
            h, (k_flat, i_flat, rings), stats, seen = carry
            l, lk = idx  # the layer, and its place among the layers of its kind
            lp = _at(params[kind], lk)
            x = _norm(c, h, lp["attn_norm"], wdtype)
            q, row, c_q = latent.project(c, z, lp, x, positions)
            if full:  # the step's rows go into the pool first: a chunk's own rows and a decode row's own are cached rows
                k_flat = k_flat.at[lk * N + blocks, offs].set(latent.to_lanes(row, k_flat.shape[-1]).astype(k_flat.dtype))
                i_flat = i_flat.at[lk * N + blocks, offs].set(latent.index_key(c, lp, x, positions).astype(i_flat.dtype))
            lats = []
            if chunk is not None and full:
                lats.append(latent.full_chunk(c, z, lp, q[:T], c_q[:T], x[:T], positions[:T], chunk[1] + lk * N, k_flat, i_flat))
            elif chunk is not None:
                lat, rings = latent.window_chunk(z, q[:T], row[:T], positions[:T], lk * S + chunk_slot, chunk[2], rings)
                lats.append(lat)
            if rows is not None and full:
                lat, chosen, scored = latent.full_rows(
                    c, z, lp, q[T:], c_q[T:], x[T:], positions[T:], rows[0] + lk * N, rows[1], k_flat, i_flat)
                lats.append(lat)
                seen = (seen[0] + chosen.astype(jnp.int32), seen[1] + scored.astype(jnp.int32))
            elif rows is not None:
                lat, rings = latent.window_rows(z, q[T:], row[T:], positions[T:], lk * S + row_slots, rows[1], rings)
                lats.append(lat)
            h = h + latent.output(c, z, lp, x, jnp.concatenate(lats) if len(lats) > 1 else lats[0])
            h, stats = ffn(dense, h, l, stats)
            return (h, (k_flat, i_flat, rings), stats, seen), None

        return body

    carry = (h, (k_flat, i_flat, rings), (jnp.int32(0), jnp.int32(0)), (jnp.int32(0), jnp.int32(0)))
    l0, at = 0, {"mla_full": 0, "mla_window": 0}
    for kind, dense, count in c.latent_groups:
        layer_ids = jnp.arange(l0, l0 + count, dtype=jnp.int32)
        carry, _ = lax.scan(layer(kind, dense), carry, (layer_ids, layer_ids - l0 + at[kind]))
        at[kind] += count
        l0 += count
    h, (k_flat, i_flat, rings), stats, seen = carry
    return (h, k_cache._replace(pool=k_flat.reshape(k_cache.pool.shape)),
            v_cache._replace(pool=i_flat.reshape(v_cache.pool.shape), slots=rings.reshape(v_cache.slots.shape)),
            dict(zip(LATENT_AUX_KEYS, stats + seen)))


def _slots_of(k_cache: SlotKv, tables: jax.Array) -> jax.Array:
    """The slot of each row: that of the sequence whose table begins with the
    row's first block (``open_slot``); a table of zeros gives scratch slot 0."""
    return k_cache.slot_of[tables[..., 0]]


def _write(k_cache: SlotKv, v_cache: SlotKv, ssm, conv, k_rows, v_rows, blocks, offs):
    """The step's one scatter of the attention layers' rows ``[L_a, R, ...]``
    into the pool, and the slot arrays as the stack left them."""
    La, R = k_rows.shape[0], k_rows.shape[1]
    layer_idx = jnp.broadcast_to(jnp.arange(La, dtype=jnp.int32)[:, None], (La, R))
    return (
        k_cache._replace(pool=_scatter_kv(k_cache.pool, layer_idx, blocks[None, :], offs[None, :], k_rows), slots=ssm),
        v_cache._replace(pool=_scatter_kv(v_cache.pool, layer_idx, blocks[None, :], offs[None, :], v_rows), slots=conv),
    )


# ---------------------------------------------------------------------------
# The step programs
# ---------------------------------------------------------------------------


def prefill(
    params: Params,
    config: ModelConfig,
    k_cache: SlotKv,
    v_cache: SlotKv,
    tokens: jax.Array,  # [T] bucket-padded token ids
    valid_len: jax.Array,  # scalar: actual new tokens
    cache_len: jax.Array,  # scalar: tokens of the sequence already computed (earlier chunks)
    block_table: jax.Array,  # [W] block ids (0 = scratch)
    all_logits: bool = False,  # static: logits of every position [T, V]
    use_flash: bool = False,
    has_prefix: bool = True,
):
    """One prefill chunk of one sequence, as ``llama.prefill``: returns
    ``(last_logits [V] | [T, V], k_cache, v_cache, aux)``. The sequence's slot
    carries its state from chunk to chunk."""
    c = config
    T = tokens.shape[0]
    h, wdtype = _embed(c, params, tokens)
    positions = cache_len + jnp.arange(T, dtype=jnp.int32)
    valid_q = jnp.arange(T, dtype=jnp.int32) < valid_len
    blocks, offs = ragged_scatter_targets(block_table, positions, valid_q, c.block_size)
    if c.is_latent:
        h, k_new, v_new, aux = _drive_latent(
            c, params, h, k_cache, v_cache, positions, blocks, offs, None, (T, block_table, valid_len), valid_q, wdtype)
        return _logits(c, params, h if all_logits else h[jnp.maximum(valid_len - 1, 0)], wdtype), k_new, v_new, aux
    attend = _chunk_attention(c, k_cache.pool, v_cache.pool, block_table, cache_len, valid_len, T, use_flash, has_prefix)
    chunk = (T, _slots_of(k_cache, block_table), valid_len)
    h, ssm, conv, k_rows, v_rows, aux = _drive(
        c, params, h, k_cache.slots, v_cache.slots, attend, positions, None, chunk, valid_q, wdtype
    )
    k_new, v_new = _write(k_cache, v_cache, ssm, conv, k_rows, v_rows, blocks, offs)
    logits = _logits(c, params, h if all_logits else h[jnp.maximum(valid_len - 1, 0)], wdtype)
    return logits, k_new, v_new, aux


def decode(
    params: Params,
    config: ModelConfig,
    k_cache: SlotKv,
    v_cache: SlotKv,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B] position of each token (its write row)
    block_tables: jax.Array,  # [B, W]
    active: jax.Array,  # [B] bool — padded rows are False (and their tables zeros)
):
    """One decode step for a batch: ``(logits [B, V], k_cache, v_cache, aux)``."""
    c = config
    h, wdtype = _embed(c, params, tokens)
    blocks, offs, _ = decode_targets(positions, block_tables, active, c.block_size)
    if c.is_latent:
        h, k_new, v_new, aux = _drive_latent(
            c, params, h, k_cache, v_cache, positions, blocks, offs, (block_tables, active), None, active, wdtype)
        return _logits(c, params, h, wdtype), k_new, v_new, aux
    attend = _rows_attention(c, k_cache.pool, v_cache.pool, block_tables, positions, active)
    slots = jnp.where(active, _slots_of(k_cache, block_tables), 0)
    h, ssm, conv, k_rows, v_rows, aux = _drive(
        c, params, h, k_cache.slots, v_cache.slots, attend, positions, slots, None, active, wdtype
    )
    k_new, v_new = _write(k_cache, v_cache, ssm, conv, k_rows, v_rows, blocks, offs)
    return _logits(c, params, h, wdtype), k_new, v_new, aux


def mixed_step(
    params: Params,
    config: ModelConfig,
    k_cache: SlotKv,
    v_cache: SlotKv,
    p_tokens: jax.Array,  # [S] prefill-chunk token ids (bucket-padded)
    p_valid: jax.Array,  # scalar i32
    p_cache_len: jax.Array,  # scalar i32
    p_table: jax.Array,  # [Wp]
    d_tokens: jax.Array,  # [B]
    d_positions: jax.Array,  # [B]
    d_tables: jax.Array,  # [B, Wd]
    d_active: jax.Array,  # [B] bool
    use_flash: bool = False,
    has_prefix: bool = True,
):
    """One mixed step, as ``llama.mixed_step``: a prefill chunk and the decode
    batch in one dispatch; ``(logits [1+B, V], k_cache, v_cache, aux)``. Every
    Mamba layer runs the chunked form on the chunk's slot and the single step
    on the decode rows' slots, between one ``in_proj`` and one ``out_proj``."""
    c = config
    S, B = p_tokens.shape[0], d_tokens.shape[0]
    p_positions = p_cache_len + jnp.arange(S, dtype=jnp.int32)
    p_valid_q = jnp.arange(S, dtype=jnp.int32) < p_valid
    h, wdtype = _embed(c, params, jnp.concatenate([p_tokens, d_tokens]))
    if c.is_latent:
        p_blocks, p_offs = ragged_scatter_targets(p_table, p_positions, p_valid_q, c.block_size)
        d_blocks, d_offs, _ = decode_targets(d_positions, d_tables, d_active, c.block_size)
        h, k_new, v_new, aux = _drive_latent(
            c, params, h, k_cache, v_cache, jnp.concatenate([p_positions, d_positions]),
            jnp.concatenate([p_blocks, d_blocks]), jnp.concatenate([p_offs, d_offs]),
            (d_tables, d_active), (S, p_table, p_valid), jnp.concatenate([p_valid_q, d_active]), wdtype)
        rows = jnp.concatenate([h[jnp.maximum(p_valid - 1, 0)][None], h[S:]], axis=0)
        return _logits(c, params, rows, wdtype), k_new, v_new, aux
    p_attend = _chunk_attention(c, k_cache.pool, v_cache.pool, p_table, p_cache_len, p_valid, S, use_flash, has_prefix)
    d_attend = _rows_attention(c, k_cache.pool, v_cache.pool, d_tables, d_positions, d_active)

    def attend(q, k, v, la):
        return jnp.concatenate([p_attend(q[:S], k[:S], v[:S], la), d_attend(q[S:], k[S:], v[S:], la)])

    chunk = (S, _slots_of(k_cache, p_table), p_valid)
    slots = jnp.where(d_active, _slots_of(k_cache, d_tables), 0)
    h, ssm, conv, k_rows, v_rows, aux = _drive(
        c, params, h, k_cache.slots, v_cache.slots, attend, jnp.concatenate([p_positions, d_positions]),
        slots, chunk, jnp.concatenate([p_valid_q, d_active]), wdtype,
    )
    p_blocks, p_offs = ragged_scatter_targets(p_table, p_positions, p_valid_q, c.block_size)
    d_blocks, d_offs, _ = decode_targets(d_positions, d_tables, d_active, c.block_size)
    k_new, v_new = _write(
        k_cache, v_cache, ssm, conv, k_rows, v_rows,
        jnp.concatenate([p_blocks, d_blocks]), jnp.concatenate([p_offs, d_offs]),
    )
    last_p = jnp.maximum(p_valid - 1, 0)
    logits = _logits(c, params, jnp.concatenate([h[last_p][None], h[S:]], axis=0), wdtype)
    return logits, k_new, v_new, aux


def decode_multi(
    params: Params,
    config: ModelConfig,
    k_cache: SlotKv,
    v_cache: SlotKv,
    tokens: jax.Array,  # [B]
    positions: jax.Array,  # [B]
    block_tables: jax.Array,  # [B, W] — must cover positions + num_steps
    active: jax.Array,  # [B] bool
    temps: jax.Array,
    top_ks: jax.Array,
    top_ps: jax.Array,
    rng_key: jax.Array,
    num_steps: int,
    return_logits: bool = False,  # static: also the per-step logits [steps, B, V]
):
    """``num_steps`` decode steps and on-device sampling in one dispatch, as
    ``llama.decode_multi``: ``(tokens_out [num_steps, B], [logits,] k_cache,
    v_cache, aux)``. The pool is read-only for the window (its rows ride a
    small carry and one scatter writes them at the end); the slot arrays are
    carried through the loop and advanced in place at every step."""
    c = config
    if c.is_latent:
        return _latent_window(params, c, k_cache, v_cache, tokens, positions, block_tables, active, temps, top_ks, top_ps,
                              rng_key, num_steps, return_logits)
    B, La, KVH, HD, bs = tokens.shape[0], c.num_attention_layers, c.num_kv_heads, c.head_dim, c.block_size
    wdtype = params["embed"].dtype
    slots = jnp.where(active, _slots_of(k_cache, block_tables), 0)

    def step(i, toks, state):
        ssm, conv, k_win, v_win = state
        h, _ = _embed(c, params, toks)
        attend = _rows_attention(c, k_cache.pool, v_cache.pool, block_tables, positions, active, window=num_steps, step=i)
        h, ssm, conv, k_rows, v_rows, aux = _drive(
            c, params, h, ssm, conv, attend, positions + i, slots, None, active, wdtype, window=(k_win, v_win)
        )
        return h, (ssm, conv, k_win.at[:, i].set(k_rows), v_win.at[:, i].set(v_rows)), aux

    win0 = jnp.zeros((La, num_steps, B, KVH, HD), dtype=wdtype)
    (ssm, conv, k_win, v_win), out, lg_steps, aux = _window(
        c, params, step, (k_cache.slots, v_cache.slots, win0, win0), tokens, temps, top_ks, top_ps, rng_key, num_steps,
        return_logits, AUX_KEYS, _stats0(c, B)[:3])
    # One scatter for the whole window: row (la, j, b) -> position_b + j.
    steps_i = jnp.arange(num_steps, dtype=jnp.int32)
    live = jnp.broadcast_to(active[None, :], (num_steps, B))
    rows = jnp.where(live, positions[None, :] + steps_i[:, None], 0)
    blocks = jnp.where(live, block_tables[jnp.arange(B)[None, :], rows // bs], 0)
    k_new, v_new = _write(
        k_cache, v_cache, ssm, conv, k_win.reshape(La, num_steps * B, KVH, HD), v_win.reshape(La, num_steps * B, KVH, HD),
        blocks.reshape(-1), (rows % bs).reshape(-1),
    )
    if return_logits:
        return out, lg_steps, k_new, v_new, aux
    return out, k_new, v_new, aux


def _window(c, params, step, state0, tokens, temps, top_ks, top_ps, rng_key, num_steps: int, return_logits: bool, aux_keys, counts0):
    """The loop of a multi-step window, for both drivers: ``step(i, tokens,
    state) -> (h, state, aux)`` is one decode step of the stack on the state the
    loop carries; the head, the sampler, the window's tokens (and logits) and
    the sums of the step log's counts are here. Returns ``(state, tokens_out
    [num_steps, B], logits, aux)``."""
    from dynamo_tpu.engine.sampling import sample_batch

    B, V = tokens.shape[0], params["embed"].shape[0]
    wdtype = params["embed"].dtype

    def body(i, carry):
        toks, *state, out, lg_out, key, counts = carry
        h, state, aux = step(i, toks, tuple(state))
        logits = _logits(c, params, h, wdtype)
        key, sub = jax.random.split(key)
        nxt = sample_batch(logits, temps, top_ks, top_ps, sub).astype(jnp.int32)
        if return_logits:
            lg_out = lg_out.at[i].set(logits)
        return (nxt, *state, out.at[i].set(nxt), lg_out, key, tuple(n + aux[name] for n, name in zip(counts, aux_keys)))

    lg0 = jnp.zeros((num_steps if return_logits else 1, B, V if return_logits else 1), jnp.float32)
    _, *state, out, lg_steps, _, counts = lax.fori_loop(
        0, num_steps, body, (tokens, *state0, jnp.zeros((num_steps, B), jnp.int32), lg0, rng_key, tuple(counts0)))
    return tuple(state), out, lg_steps, dict(zip(aux_keys, counts))


def _latent_window(params, c, k_cache, v_cache, tokens, positions, block_tables, active, temps, top_ks, top_ps, rng_key,
                   num_steps: int, return_logits: bool):
    """``decode_multi`` of a stack of latent layers: every step is a ``decode``
    step (its rows written into the pool and the rings before it attends), so
    the whole cache is the state ``_window`` carries, written in place."""
    wdtype = params["embed"].dtype

    def step(i, toks, state):
        h, _ = _embed(c, params, toks)
        blocks, offs, _ = decode_targets(positions + i, block_tables, active, c.block_size)
        h, k, v, aux = _drive_latent(c, params, h, *state, positions + i, blocks, offs, (block_tables, active), None, active, wdtype)
        return h, (k, v), aux

    (k_new, v_new), out, lg_steps, aux = _window(
        c, params, step, (k_cache, v_cache), tokens, temps, top_ks, top_ps, rng_key, num_steps, return_logits,
        LATENT_AUX_KEYS, (jnp.int32(0),) * len(LATENT_AUX_KEYS))
    if return_logits:
        return out, lg_steps, k_new, v_new, aux
    return out, k_new, v_new, aux
