"""Paged KV cache: device arrays + host-side block allocator with prefix
caching and KV event emission.

The device cache is a global block pool: ``k``/``v`` arrays of shape
``[layers, num_blocks, block_size, kv_heads * head_dim]`` (the layout
contract is in ``KvCacheArrays``). Sequences own *block tables* (lists of
block indices); attention gathers through them.
This is the TPU-native equivalent of vLLM's paged KV plus the engine-side
part of the reference's KVBM G1 tier (lib/llm/src/block_manager — device
pool, sequence-hash reuse in block/registry.rs:478, pool/managed.rs
active/inactive sets with eviction).

Prefix caching: completed full blocks are registered under their chained
block hash (``dynamo_tpu.llm.tokens``). New sequences match their prefix
hashes against the registry and skip prefill for matched blocks. Eviction is
LRU over unreferenced cached blocks. Every register/evict emits a KV event
for the KV-aware router (ref: kv_router/publisher.rs — the engine→router
event loop, SURVEY.md §3D).
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.llm.tokens import BlockHash


class QuantKv(NamedTuple):
    """int8-quantized KV tensor: values + per-(token, head) symmetric scale.

    A pytree, so it flows through jit args, scan xs, and donation exactly
    like a plain array — model code dispatches on the type at gather/scatter
    points (``dequantize_kv`` / ``quantize_kv_rows``)."""

    q: jax.Array  # int8, [L, N, BS, KVH*HD]
    scale: jax.Array  # f32, [L, N, BS, KVH]

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype


class SlotKv(NamedTuple):
    """One side of a hybrid model's cache (``ModelConfig.layer_types``): the
    attention layers' paged pool and, beside it, one of the two slot arrays of
    the state-space layers. A running sequence holds one *slot* from admission
    to finish or preemption, whatever its length (``SlotAllocator``); slot 0 is
    the scratch sink that padded rows read and write, as block 0 is the pool's.

    ``k`` carries the recurrent state ``[L_m, S, *mamba_state_shape]``
    (float32; ``ModelConfig.mamba_state_shape``: heads side by side on the
    lanes, ``d_state`` on the sublanes) and the map ``slot_of`` from a block to the slot of the sequence
    whose table *begins* with that block; ``v`` carries the convolution's last
    ``d_conv - 1`` input columns ``[L_m, S, d_conv - 1, conv_dim]`` (compute
    dtype; the columns are the leading axis of a slot so that the lane axis is
    ``conv_dim``). A step program finds a row's slot as ``slot_of[table[0]]``
    (``hybrid.open_slot`` writes the entry when it zeroes the slot), so its
    signature is that of every other family's: a table of zeros reads and
    writes the scratch slot. A pytree: it flows through jit arguments and
    donation like a plain array.

    A stack of "cca" layers has BOTH in every layer: a pool row a token and a
    slot a sequence. Its slot holds columns only, so ``v`` carries them,
    ``[L_cca, S, cca_slot_lanes]`` (the first convolution's last input, the
    second's, and the last token's projection for the shifted value heads,
    side by side on the lanes), and ``k`` carries an array of no elements
    ``[L_cca, S, 0]`` beside ``slot_of``.

    A stack of latent layers ("mla_full" / "mla_window", ``models/latent.py``)
    has a pool row a token in its full layers only, and its width is the
    kind's, not ``KVH*HD``: the ``k`` pool holds ``[latent ; rotated key]``
    (``kv_lora_rank + qk_rope_head_dim`` lanes, and zeros up to whole tiles of
    128) and the ``v`` pool the
    indexer's key (``index_head_dim`` lanes), both ``[L_full, N, BS, lanes]``.
    A window layer holds no blocks: its last ``sliding_window`` rows are a
    ring in the sequence's slot, ``v`` slots ``[L_window, S, sliding_window,
    swa_kv_lora_rank + swa_qk_rope_head_dim]`` (position ``t`` lies in row ``t
    mod sliding_window``), and ``k`` carries ``[L_window, S, 0]`` beside
    ``slot_of``."""

    pool: Any  # jax.Array — [L_a, N, BS, KVH*HD]
    slots: jax.Array
    slot_of: Optional[jax.Array] = None  # [N] i32, on the k side only

    @property
    def shape(self):
        return self.pool.shape

    @property
    def dtype(self):
        return self.pool.dtype


def pool_of(cache):
    """The paged pool of one side of the cache, whatever rides beside it."""
    return cache.pool if isinstance(cache, SlotKv) else cache


def quantize_kv_rows(rows: jax.Array) -> QuantKv:
    """Symmetric int8 quantization of rows ``[..., KVH, HD]`` over the
    head_dim axis, returned in the pool's layout: codes ``[..., KVH*HD]``,
    scales ``[..., KVH]``."""
    amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(rows.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return QuantKv(merge_heads(q), scale[..., 0])


def dequantize_kv(x: QuantKv, dtype=jnp.bfloat16) -> jax.Array:
    """QuantKv in the pool's layout → real-valued rows ``[..., KVH, HD]``."""
    rows = split_heads(x.q, x.scale.shape[-1]).astype(jnp.float32) * x.scale[..., None]
    return rows.astype(dtype)


def merge_heads(rows: jax.Array) -> jax.Array:
    """``[..., KVH, HD]`` → ``[..., KVH*HD]``: rows enter the pool's layout.
    For the rows a step writes or a block in transit, never for the pool."""
    return rows.reshape(*rows.shape[:-2], rows.shape[-2] * rows.shape[-1])


def split_heads(rows: jax.Array, num_kv_heads: int) -> jax.Array:
    """``[..., KVH*HD]`` → ``[..., KVH, HD]``: what was gathered from the
    pool leaves its layout. Its cost is the context gathered."""
    return rows.reshape(*rows.shape[:-1], num_kv_heads, rows.shape[-1] // num_kv_heads)


def layer_flat(cache):
    """``[L, N, ...]`` → ``[L*N, ...]`` for an array or a QuantKv: the one
    reshape of the pool a step program may hold. It merges leading
    dimensions only, so no element changes tile and XLA emits a bitcast."""
    return jax.tree.map(lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), cache)


def cache_rows(config: ModelConfig, positions):
    """Row of its sequence's block table that holds position ``t``: THE place
    that relates a token's position to its cache row. Works alike on Python
    ints (the scheduler's block accounting) and on traced arrays (the step
    programs' scatter targets and prefix lengths).

    A causal model keeps one row a token for ever: the row *is* the position.
    An eva model keeps ``M = window_size // chunk_size`` summary rows for each
    completed window in front of the current window's exact rows, so
    ``row(t) = M * (t // W) + t % W``: a query at ``t`` attends exactly the
    rows below its own (summaries of every earlier window, then the earlier
    keys of its own window), which is what the prefix length of every
    attention path means. The table of a sequence of ``n`` tokens is
    ``cache_rows(config, n - 1) + 1`` rows once its completed windows are
    rolled (``llama.eva_roll``)."""
    if not config.is_eva:
        return positions
    W = config.window_size
    return config.summaries_per_window * (positions // W) + positions % W


def ragged_scatter_targets(
    block_table: jax.Array,  # [W] block ids for one sequence (0 = scratch)
    positions: jax.Array,  # [T] cache row per token row (``cache_rows``)
    live: jax.Array,  # [T] bool — dead rows (bucket padding) sink to block 0
    block_size: int,
):
    """Paged-KV scatter targets for a ragged run of token rows sharing one
    block table (a prefill chunk, or one sequence's slice of a mixed
    batch). Returns ``(tgt_blocks [T], tgt_offs [T])``; dead rows target
    the reserved scratch block 0 so no real block is corrupted. Shared by
    ``llama.prefill`` and ``llama.mixed_step`` so the per-row position →
    (block, offset) convention lives in one place."""
    slots = jnp.where(live, positions, 0)
    return jnp.where(live, block_table[slots // block_size], 0), slots % block_size


@dataclass
class KvCacheArrays:
    """Device-side block pool (one array pair covering all layers). With
    ``config.kv_cache_dtype == "int8"`` the members are :class:`QuantKv`
    pytrees instead of plain arrays.

    **Layout contract.** ``k``/``v`` are ``[L, N, BS, KVH*HD]``: a token's
    heads are merged into one lane axis, which is the page
    ``(1, BS, KVH*HD)`` that the attention kernels' ``BlockSpec`` reads
    (attention/megakernel.py, attention/decode.py). A QuantKv's codes take
    the same shape and its scales are ``[L, N, BS, KVH]``; MLA's one latent
    row per token is ``[L, N, BS, width]`` by the same rule (``kv_heads``
    1). The pool is allocated in this layout and keeps it for life: un-
    merging ``(KVH, HD)`` moves every element to another (8, 128) tile, so
    on a chip it is a copy of the whole pool, and no step program may hold
    one. Writers merge the rows they write (``merge_heads``), gathering
    readers split what they gathered (``split_heads``), transfers reshape
    one block at the boundary; ``layer_flat`` is the only reshape applied
    to the pool itself. ``tests/test_kv_layout.py`` holds the step
    programs to this. Sharding over ``tp`` is on axis 3: a contiguous
    ``KVH*HD/tp`` slice is ``KVH/tp`` whole heads."""

    k: Any  # jax.Array | QuantKv | SlotKv — [L, N, BS, KVH*HD]
    v: Any
    kv_heads: int = 1  # KVH of the merged axis (1 for MLA's latent row)

    @classmethod
    def create(
        cls,
        config: ModelConfig,
        num_blocks: int,
        dtype=jnp.bfloat16,
        sharding: Optional[jax.sharding.Sharding] = None,
        num_slots: int = 0,
    ) -> "KvCacheArrays":
        """``L`` is the number of attention layers: all of them, or for a
        hybrid model those ``layer_types`` names "attention" or "cca" (its
        state-space layers hold ``num_slots`` slots instead, its cca layers
        beside the pool, scratch slot 0 included: ``SlotKv``)."""
        if sharding is not None:
            config.refuse_for_layer_types("a sharded cache (a mesh)")
        if config.is_latent:
            return cls._create_latent(config, num_blocks, dtype, num_slots)
        if config.architecture == "mla":
            # MLA stores one shared latent row per token (kv_lora_rank +
            # rope dim) in ``k``; ``v`` is a placeholder (values decompress
            # from the latent — models/mla.py). int8 quantizes the latent
            # row with one per-token scale (the row is rms-normed latent ‖
            # rope'd keys — O(1) ranges, one scale holds within a code step).
            kv_heads, lanes = 1, config.kv_lora_rank + config.qk_rope_head_dim
        else:
            kv_heads, lanes = config.num_kv_heads, config.num_kv_heads * config.head_dim
        rows = (config.num_attention_layers, num_blocks, config.block_size)

        def zeros(shape, dt):
            init = jnp.zeros(shape, dtype=dt)
            return jax.device_put(init, sharding) if sharding is not None else init

        def mk():
            if config.kv_cache_dtype == "int8":
                return QuantKv(zeros((*rows, lanes), jnp.int8), zeros((*rows, kv_heads), jnp.float32))
            return zeros((*rows, lanes), dtype)

        v = jnp.zeros((config.num_layers, 1, 1, 1), dtype=dtype) if config.architecture == "mla" else mk()
        k = mk()
        if config.is_hybrid:
            if num_slots < 2:
                raise ValueError("a hybrid model's cache needs the scratch slot and at least one more (num_slots >= 2)")
            c, Lm, Lc = config, config.num_mamba_layers, config.num_cca_layers
            if Lc:
                state = jnp.zeros((Lc, num_slots, 0), dtype)
                columns = jnp.zeros((Lc, num_slots, c.cca_slot_lanes), dtype)
            else:
                state = jnp.zeros((Lm, num_slots, *c.mamba_state_shape), jnp.float32)
                columns = jnp.zeros((Lm, num_slots, c.mamba_d_conv - 1, c.mamba_conv_dim), dtype)
            k = SlotKv(k, state, jnp.zeros((num_blocks,), jnp.int32))
            v = SlotKv(v, columns)
        return cls(k=k, v=v, kv_heads=kv_heads)


    @classmethod
    def _create_latent(cls, config: ModelConfig, num_blocks: int, dtype, num_slots: int) -> "KvCacheArrays":
        """The cache of a stack of latent layers (``SlotKv``)."""
        if config.kv_cache_dtype == "int8":
            config.refuse_for_layer_types("kv_cache_dtype 'int8'")
        if num_slots < 2:
            raise ValueError("a hybrid model's cache needs the scratch slot and at least one more (num_slots >= 2)")
        c, Lf, Lw = config, config.num_attention_layers, config.num_window_layers
        rows = (Lf, num_blocks, c.block_size)
        ring = c.latent_sizes("mla_window").row if Lw else 0
        # Whole 128-lane tiles: a buffer whose last axis is not gets its TOKENS on the lanes from the runtime, and every
        # step program then re-lays the whole pool on entry and on exit (5.8 ms each way at 1.8 GB: PERF.md section 6, PR 50).
        k = SlotKv(jnp.zeros((*rows, -(-c.latent_sizes("mla_full").row // 128) * 128 if Lf else 0), dtype),
                   jnp.zeros((Lw, num_slots, 0), dtype), jnp.zeros((num_blocks,), jnp.int32))
        v = SlotKv(jnp.zeros((*rows, c.index_head_dim if Lf else 0), dtype),
                   jnp.zeros((Lw, num_slots, c.sliding_window, ring), dtype))
        return cls(k=k, v=v, kv_heads=1)


class OutOfBlocksError(Exception):
    pass


class SlotAllocator:
    """Host-side bookkeeping of the state slots beside the block pool: a
    sequence takes one at admission and gives it back at finish or preemption.
    Slot 0 is the scratch sink and is never handed out."""

    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self._free: List[int] = list(range(num_slots - 1, 0, -1))
        self.allocs_total = 0

    @property
    def in_use(self) -> int:
        return self.num_slots - 1 - len(self._free)

    def allocate(self) -> int:
        if not self._free:
            raise OutOfBlocksError("no state slot free")
        self.allocs_total += 1
        return self._free.pop()

    def release(self, slot: int) -> None:
        if not 0 < slot < self.num_slots or slot in self._free:
            raise ValueError(f"slot {slot} is not held")
        self._free.append(slot)


@dataclass
class KvEvent:
    """Engine→router cache event (ref: kv-cache-events consumed by
    KvIndexer.apply_event, indexer.rs)."""

    kind: str  # "stored" | "removed"
    block_hashes: List[int]
    parent_hash: Optional[int] = None
    ts: float = field(default_factory=time.time)

    def to_wire(self) -> dict:
        return {
            "kind": self.kind,
            "block_hashes": [h & 0xFFFFFFFFFFFFFFFF for h in self.block_hashes],
            "parent_hash": self.parent_hash,
            "ts": self.ts,
        }


class BlockAllocator:
    """Host-side bookkeeping for the device block pool.

    Block states (mirrors pool/managed.rs active/inactive):
    - free      — on the free list, contents dead.
    - active    — referenced by ≥1 live sequence (refcount > 0).
    - cached    — refcount 0 but registered under a block hash; evictable LRU.
    """

    def __init__(self, num_blocks: int, on_event: Optional[Callable[[KvEvent], None]] = None):
        self.num_blocks = num_blocks
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._refcount: Dict[int, int] = {}
        # block_hash -> block_id for completed, reusable blocks.
        self._by_hash: Dict[BlockHash, int] = {}
        self._hash_of: Dict[int, BlockHash] = {}
        # LRU over cached (refcount-0, hashed) blocks.
        self._cached_lru: "OrderedDict[int, None]" = OrderedDict()
        self.on_event = on_event
        # KVBM offload hook: called (block_id, block_hash) when a cached
        # block is evicted for reuse — the copy-out point for the G1→G2
        # cascade (content is still intact at call time).
        self.on_evict: Optional[Callable[[int, int], None]] = None
        # Prefix-cache accounting (monotonic; surfaced through worker stats
        # → aggregator counters → the Grafana hit-rate panels).
        self.hit_blocks_total = 0
        self.miss_blocks_total = 0
        self.evicted_blocks_total = 0

    # --- queries ------------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._cached_lru)

    @property
    def num_active(self) -> int:
        return sum(1 for c in self._refcount.values() if c > 0)

    @property
    def num_cached(self) -> int:
        return len(self._cached_lru)

    def usage(self) -> float:
        return 1.0 - len(self._free) / max(self.num_blocks, 1)

    # --- prefix matching ----------------------------------------------------
    def match_prefix(self, block_hashes: Sequence[BlockHash]) -> List[int]:
        """Longest prefix of ``block_hashes`` present in cache; acquires a
        reference on each matched block (caller owns them)."""
        matched: List[int] = []
        for h in block_hashes:
            bid = self._by_hash.get(h)
            if bid is None:
                break
            self._acquire(bid)
            matched.append(bid)
        self.hit_blocks_total += len(matched)
        self.miss_blocks_total += len(block_hashes) - len(matched)
        return matched

    def ref_count(self, bid: int) -> int:
        """Live references on a block (0 = cached/free). The scheduler's
        copy-on-write check: a matched block with other holders must not be
        written in place."""
        return self._refcount.get(bid, 0)

    # --- allocation ---------------------------------------------------------
    def allocate(self, n: int) -> List[int]:
        """Take n fresh blocks, evicting LRU cached blocks as needed."""
        out: List[int] = []
        removed_hashes: List[int] = []
        try:
            for _ in range(n):
                if self._free:
                    bid = self._free.pop()
                elif self._cached_lru:
                    bid, _ = self._cached_lru.popitem(last=False)  # LRU evict
                    h = self._hash_of.pop(bid)
                    del self._by_hash[h]
                    removed_hashes.append(h)
                    self.evicted_blocks_total += 1
                    if self.on_evict is not None:
                        self.on_evict(bid, h)  # offload cascade copy-out
                else:
                    raise OutOfBlocksError(f"need {n} blocks, {len(out)} available")
                self._refcount[bid] = 1
                out.append(bid)
        except OutOfBlocksError:
            for bid in out:
                self.release([bid])
            raise
        finally:
            if removed_hashes and self.on_event:
                self.on_event(KvEvent(kind="removed", block_hashes=removed_hashes))
        return out

    def _acquire(self, bid: int) -> None:
        c = self._refcount.get(bid, 0)
        if c == 0 and bid in self._cached_lru:
            del self._cached_lru[bid]
        self._refcount[bid] = c + 1

    def acquire(self, block_ids: Sequence[int]) -> None:
        for bid in block_ids:
            self._acquire(bid)

    def release(self, block_ids: Sequence[int]) -> None:
        """Drop a reference; refcount-0 blocks become cached (if hashed) or
        free (if not).

        Blocks enter the LRU in REVERSE list order. Block tables are
        chain-ordered (prefix head first), and a chained prefix is only
        matchable up to its first missing block — evicting a chain HEAD
        destroys the whole prefix while its tail blocks sit uselessly in
        cache. Reversing makes eviction consume chains tail-first: matches
        degrade to shorter prefixes instead of zero, and per-request suffix
        blocks (unique, never re-matched) go before shared prefix heads."""
        for bid in reversed(list(block_ids)):
            c = self._refcount.get(bid, 0) - 1
            if c > 0:
                self._refcount[bid] = c
                continue
            self._refcount.pop(bid, None)
            if bid in self._hash_of:
                self._cached_lru[bid] = None
                self._cached_lru.move_to_end(bid)
            else:
                self._free.append(bid)

    # --- hash registration --------------------------------------------------
    def register_hashes(self, block_ids: Sequence[int], block_hashes: Sequence[BlockHash]) -> None:
        """Publish completed blocks for reuse (ref: block/registry.rs).
        Emits a ``stored`` KV event."""
        stored: List[int] = []
        event_parent: Optional[int] = None
        parent: Optional[int] = None  # hash of the previous block in the chain
        for bid, h in zip(block_ids, block_hashes):
            if bid in self._hash_of:
                parent = self._hash_of[bid]
                continue
            existing = self._by_hash.get(h)
            if existing is not None and existing != bid:
                # Duplicate content: keep the existing registration.
                parent = h
                continue
            self._by_hash[h] = bid
            self._hash_of[bid] = h
            if not stored:
                event_parent = parent  # chain linkage for the router index
            stored.append(h)
            parent = h
        if stored and self.on_event:
            self.on_event(KvEvent(kind="stored", block_hashes=stored, parent_hash=event_parent))

    def touch(self, block_ids: Sequence[int]) -> None:
        for bid in block_ids:
            if bid in self._cached_lru:
                self._cached_lru.move_to_end(bid)

    def clear_cached(self) -> int:
        """Drop all refcount-0 cached blocks (ref: clear_kv_blocks endpoint,
        http/service/clear_kv_blocks.rs). Returns count cleared."""
        n = len(self._cached_lru)
        removed = []
        for bid in list(self._cached_lru):
            h = self._hash_of.pop(bid)
            del self._by_hash[h]
            removed.append(h)
            self._free.append(bid)
        self._cached_lru.clear()
        self.evicted_blocks_total += n
        if removed and self.on_event:
            self.on_event(KvEvent(kind="removed", block_hashes=removed))
        return n
