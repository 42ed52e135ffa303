"""KVBM: multi-tier KV block management (HBM → host DRAM → disk).

Ref: lib/llm/src/block_manager (20k LoC) — ``KvBlockManager``
(block_manager.rs:99), tiers ``CacheLevel::{G1,G2,G3,G4}`` (:62-75),
offload cascade on registration/eviction (offload.rs), onboarding
(``onboard_blocks`` :144), sequence-hash registry (block/registry.rs:478).

TPU-native mapping:
- **G1** — device HBM: the engine's paged ``KvCacheArrays`` + BlockAllocator.
- **G2** — host DRAM: numpy block pool, filled by the offload cascade when G1
  evicts a cached block (copy-out happens *before* reuse via the allocator's
  eviction hook). The reference's ``block_copy.cu`` kernels become jitted XLA
  gather/scatter + ``jax.device_get/put`` DMA (transfer.py).
- **G3** — local disk: file-per-block spill from G2 eviction.
- **G4** — remote pool: hash-addressed blocks in the control-plane object
  store (storage.RemotePool), filled by G3 (or G2) spill and onboardable by
  ANY worker — the cross-host tier (ref: CacheLevel::G4
  block_manager.rs:62-75).

Lookup walks tiers: G1 hit ⇒ free; G2/G3 hit ⇒ *onboard* (copy back into
freshly allocated G1 blocks) — still far cheaper than recomputing prefill
(the reference reports +40% TTFT from host offload alone, SURVEY.md §6).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from dynamo_tpu.engine.kv_cache import BlockAllocator, KvCacheArrays
from dynamo_tpu.llm.block_manager.storage import DiskPool, HostPool
from dynamo_tpu.llm.block_manager.transfer import (
    gather_blocks_async,
    scatter_blocks_device,
)
from dynamo_tpu.runtime.logging import get_logger

logger = get_logger(__name__)


class CacheLevel(enum.Enum):
    G1 = "device"
    G2 = "host"
    G3 = "disk"
    G4 = "remote"


@dataclass
class KvbmMetrics:
    offloads_g2: int = 0
    offloads_g3: int = 0
    offloads_g4: int = 0
    onboards_g2: int = 0
    onboards_g3: int = 0
    onboards_g4: int = 0
    matched_tokens_g1: int = 0
    matched_tokens_tiered: int = 0


@dataclass
class TieredMatch:
    """Result of a tiered prefix lookup."""

    g1_blocks: List[int] = field(default_factory=list)  # device blocks, ref-acquired
    onboardable: List[Tuple[int, CacheLevel]] = field(default_factory=list)  # (hash, tier)

    @property
    def total_blocks(self) -> int:
        return len(self.g1_blocks) + len(self.onboardable)


class KvBlockManager:
    """Owns the tier hierarchy around a device cache + allocator."""

    def __init__(
        self,
        cache: KvCacheArrays,
        allocator: BlockAllocator,
        *,
        host_blocks: int = 0,
        disk_dir: Optional[str] = None,
        disk_blocks: int = 0,
    ):
        self.cache = cache
        self.allocator = allocator
        self.host = HostPool(capacity=host_blocks) if host_blocks > 0 else None
        self.disk = DiskPool(disk_dir, capacity=disk_blocks) if disk_dir and disk_blocks > 0 else None
        self.remote = None  # G4 — attach_remote()
        self.metrics = KvbmMetrics()
        # Async offload: eviction snapshots the block ON DEVICE (dispatch-
        # ordered, no host sync — the old inline gather stalled every
        # admission on a device→host DMA under memory pressure, ref's
        # equivalent machinery: block_manager/offload.rs pending queues);
        # the host transfer happens in one batched drain.
        self._pending: Dict[int, Tuple] = {}
        self._pending_cap = 32
        allocator.on_evict = self._offload_block

    def attach_remote(self, remote) -> None:
        """Enable the G4 remote tier (storage.RemotePool): deepest-spill
        target of the offload cascade, onboardable by any worker sharing the
        object store."""
        self.remote = remote

    # --- offload cascade (G1 → G2 → G3 → G4) --------------------------------
    def _offload_block(self, block_id: int, block_hash: int) -> None:
        """Eviction hook — runs on the scheduler's admission path, so it
        must not block: queue a device-side snapshot and return."""
        if self.host is None:
            return
        if (
            block_hash in self._pending
            or self.host.has(block_hash)
            or (self.disk is not None and self.disk.has(block_hash))
        ):
            return
        self._pending[block_hash] = gather_blocks_async(self.cache, block_id)
        if len(self._pending) >= self._pending_cap:
            self.flush_pending()

    def flush_pending(self) -> int:
        """Drain queued offload snapshots to the host tier in ONE batched
        device→host transfer. Called when the queue fills, before tier
        lookups (pending blocks must be onboardable), and at shutdown."""
        if not self._pending:
            return 0
        items, self._pending = list(self._pending.items()), {}
        import jax

        flat = jax.device_get([d for _, pair in items for d in pair if d is not None])
        it = iter(flat)
        for h, (k_dev, v_dev) in items:
            k_np = np.asarray(next(it))
            v_np = np.asarray(next(it)) if v_dev is not None else np.zeros((0,), k_np.dtype)
            self._cascade_put(h, k_np, v_np)
        return len(items)

    def _cascade_put(self, block_hash: int, k_np: np.ndarray, v_np: np.ndarray) -> None:
        spilled = self.host.put(block_hash, k_np, v_np)
        self.metrics.offloads_g2 += 1
        if spilled is not None and self.disk is not None:
            sh, sk, sv = spilled
            if not self.disk.has(sh):
                spilled = self.disk.put(sh, sk, sv)
                self.metrics.offloads_g3 += 1
            else:
                spilled = None
        if spilled is not None and self.remote is not None:
            sh, sk, sv = spilled
            if not self.remote.has(sh):
                self.remote.put(sh, sk, sv)
                self.metrics.offloads_g4 += 1

    # --- tiered lookup ------------------------------------------------------
    def match_prefix(self, block_hashes: Sequence[int]) -> TieredMatch:
        """Longest-prefix match across tiers. G1 blocks come back
        ref-acquired; deeper-tier hits come back as onboard candidates.
        The chain must stay contiguous: a tier miss ends the walk."""
        self.flush_pending()  # pending snapshots become G2-visible here
        match = TieredMatch()
        g1 = self.allocator.match_prefix(block_hashes)
        match.g1_blocks = g1
        self.metrics.matched_tokens_g1 += len(g1)
        for h in block_hashes[len(g1) :]:
            if self.host is not None and self.host.has(h):
                match.onboardable.append((h, CacheLevel.G2))
            elif self.disk is not None and self.disk.has(h):
                match.onboardable.append((h, CacheLevel.G3))
            elif self.remote is not None and self.remote.has(h):
                match.onboardable.append((h, CacheLevel.G4))
            else:
                break
        self.metrics.matched_tokens_tiered += len(match.onboardable)
        return match

    # --- onboarding (ref: onboard_blocks block_manager.rs:144) --------------
    def onboard(self, match: TieredMatch, block_hashes: Sequence[int]) -> List[int]:
        """Copy onboardable blocks into fresh G1 blocks; returns the full
        ref-held device block list (g1 + onboarded). On allocation failure the
        match degrades to its G1 prefix (caller prefills the rest).

        The device write is ASYNC: every onboarded block rides ONE stacked
        host→device upload plus one fused scatter dispatch — no host sync —
        so the caller's uncached-suffix prefill enqueues right behind the
        onboard on the device stream. A warm-DRAM hit overlaps its copy-back
        with the suffix compute instead of stalling admission on per-block
        DMAs (the per-block scatter_blocks loop it replaces)."""
        if not match.onboardable:
            return match.g1_blocks
        try:
            new_blocks = self.allocator.allocate(len(match.onboardable))
        except Exception:
            match.onboardable = []
            return match.g1_blocks
        entries = []
        for i, (h, tier) in enumerate(match.onboardable):
            if tier == CacheLevel.G2:
                entry = self.host.get(h)
                self.metrics.onboards_g2 += 1
            elif tier == CacheLevel.G3:
                entry = self.disk.get(h)
                self.metrics.onboards_g3 += 1
            else:
                entry = self.remote.get(h)
                self.metrics.onboards_g4 += 1
            if entry is None:  # raced out of the pool — stop onboarding here
                self.allocator.release(new_blocks[i:])
                match.onboardable = match.onboardable[:i]
                new_blocks = new_blocks[:i]
                break
            entries.append(entry)
        if not new_blocks:
            return match.g1_blocks
        import jax.numpy as jnp

        k_stack = jnp.asarray(np.stack([k for k, _ in entries], axis=1))
        v_stack = (
            jnp.asarray(np.stack([v for _, v in entries], axis=1))
            if entries[0][1].size
            else None
        )
        scatter_blocks_device(self.cache, new_blocks, k_stack, v_stack)
        # Register the onboarded blocks under their hashes so future requests
        # hit them in G1 directly.
        n_g1 = len(match.g1_blocks)
        hashes = list(block_hashes[n_g1 : n_g1 + len(new_blocks)])
        self.allocator.register_hashes(new_blocks, hashes)
        return match.g1_blocks + new_blocks

    # --- introspection ------------------------------------------------------
    def usage(self) -> Dict[str, float]:
        out = {"g1": self.allocator.usage()}
        if self.host is not None:
            out["g2"] = self.host.usage()
        if self.disk is not None:
            out["g3"] = self.disk.usage()
        if self.remote is not None:
            out["g4_known_blocks"] = float(len(self.remote))
        return out

    def reset_tier(self, level: CacheLevel) -> int:
        """Ref: block_manager/controller.rs reset endpoints."""
        if level == CacheLevel.G1:
            return self.allocator.clear_cached()
        if level == CacheLevel.G2 and self.host is not None:
            self._pending.clear()
            return self.host.clear()
        if level == CacheLevel.G3 and self.disk is not None:
            return self.disk.clear()
        return 0
