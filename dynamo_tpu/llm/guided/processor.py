"""Device-side application of token FSMs: the mask pool + per-sequence state.

All compiled grammars share ONE device-resident mask pool — a
``[pool_rows, ceil(V/32)] uint32`` array where each grammar occupies a
contiguous block of rows (one row per FSM state) starting at its base
offset. Row 0 is reserved as the allow-everything row, so unguided rows in
a mixed batch map to row 0 and pass through the masked sampler unchanged —
one compiled executable serves every guided/unguided batch composition.

The pool's capacity is bucketed (pow2 growth from
``SchedulerConfig.guided_pool_rows``), matching the repo's bucketed-compile
discipline: the masked-sampling executable's shape only changes when total
registered FSM states outgrow the current bucket, and ``Scheduler.warmup``
precompiles it at the initial bucket — so guided rows joining a warmed batch
add zero post-warmup XLA compiles.

Per step, the scheduler packs one i32 row id per batch row
(``pool_base + fsm_state``); the jit'd sampler gathers the mask row and adds
``-inf`` to disallowed logits (engine/sampling.py ``apply_token_masks``).
The FSM *advance* is a host-side O(1) table lookup on the sampled token the
scheduler already reads back — no extra device↔host sync anywhere.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from dynamo_tpu.llm.guided.fsm import FsmCache, TokenFSM, compile_token_fsm
from dynamo_tpu.llm.guided.grammar import GrammarError, compile_regex, spec_to_pattern
from dynamo_tpu.runtime.logging import get_logger

logger = get_logger(__name__)


class GuidedMaskPool:
    """Shared device mask pool: one row per FSM state across all live
    grammars, row 0 = allow-all (the unguided pass-through)."""

    def __init__(self, vocab_size: int, min_rows: int = 1024):
        self.vocab_size = vocab_size
        self.words = (vocab_size + 31) // 32
        self.capacity = max(int(min_rows), 2)
        self._host = np.zeros((self.capacity, self.words), dtype=np.uint32)
        self._host[0] = self._allow_all_row()
        self._used = 1
        self._bases: Dict[int, int] = {}  # id(fsm) -> base row
        self._keep: List[TokenFSM] = []  # pin fsms so id() stays stable
        self._device = None

    def _allow_all_row(self) -> np.ndarray:
        row = np.full((self.words,), 0xFFFFFFFF, dtype=np.uint32)
        tail = self.vocab_size & 31
        if tail:
            row[-1] = np.uint32((1 << tail) - 1)  # pad bits stay 0
        return row

    def register(self, fsm: TokenFSM) -> int:
        """Ensure ``fsm``'s mask rows are in the pool; returns its base row.
        Growing past the capacity bucket doubles it (a new executable shape,
        logged — size ``guided_pool_rows`` to your grammar working set)."""
        base = self._bases.get(id(fsm))
        if base is not None:
            return base
        need = self._used + fsm.num_states
        if need > self.capacity:
            cap = self.capacity
            while cap < need:
                cap *= 2
            logger.warning(
                "guided mask pool grew %d -> %d rows (masked-sampling "
                "executables recompile at the new shape)", self.capacity, cap,
            )
            host = np.zeros((cap, self.words), dtype=np.uint32)
            host[: self._used] = self._host[: self._used]
            self._host = host
            self.capacity = cap
        base = self._used
        self._host[base : base + fsm.num_states] = fsm.allow_words
        self._used = base + fsm.num_states
        self._bases[id(fsm)] = base
        self._keep.append(fsm)
        self._device = None  # re-upload lazily
        return base

    def device(self):
        """Device copy of the pool, padded to the capacity bucket. Uploaded
        once per registration, not per step."""
        if self._device is None:
            import jax.numpy as jnp

            self._device = jnp.asarray(self._host)
        return self._device


class GuidedState:
    """Per-sequence FSM cursor, advanced host-side from each sampled token."""

    __slots__ = ("fsm", "pool_base", "state", "finished", "from_cache")

    def __init__(self, fsm: TokenFSM, pool_base: int, from_cache: bool = False):
        self.fsm = fsm
        self.pool_base = pool_base
        self.state = 0
        self.finished = False
        self.from_cache = from_cache

    @property
    def row_id(self) -> int:
        """Mask-pool row for the current state (allow-all row once done —
        the sequence stops before it would sample again)."""
        if self.state < 0 or self.finished:
            return 0
        return self.pool_base + self.state

    @property
    def exhausted(self) -> bool:
        """The grammar is complete (or unrecoverable): force-finish with
        ``finish_reason="stop"`` — the FSM accepts and only EOS remains."""
        if self.finished or self.state < 0:
            return True
        return bool(self.fsm.accept_only[self.state])

    def advance(self, token: int) -> None:
        if self.finished:
            return
        if token in self.fsm.eos_ids:
            self.finished = True
            return
        if 0 <= token < self.fsm.vocab_size and self.state >= 0:
            self.state = int(self.fsm.next_state[self.state, token])
        else:
            self.state = -1
        if self.state < 0:
            # Only possible when something outside the mask forced a token
            # (host logits processor, logit_bias): stop rather than emit
            # unconstrained text under a structured-output contract.
            self.finished = True


class GuidedDecoder:
    """Scheduler-owned facade: spec → cached token FSM → pool registration.

    Counters feed the worker stats scrape (``guided_requests_total``,
    grammar-compile totals) through ``stats()``."""

    def __init__(
        self,
        tokenizer,
        *,
        eos_ids: Sequence[int] = (),
        vocab_size: Optional[int] = None,
        pool_rows: int = 1024,
        cache_size: int = 64,
    ):
        self.tokenizer = tokenizer
        self.vocab_size = int(vocab_size or tokenizer.vocab_size)
        self.eos_ids = list(eos_ids) or list(getattr(tokenizer, "eos_token_ids", []) or [])
        self.pool = GuidedMaskPool(self.vocab_size, min_rows=pool_rows)
        self.cache = FsmCache(maxsize=cache_size)
        self._token_strs: Optional[List[str]] = None
        self.requests_total = 0
        self.compiles_total = 0
        self.compile_seconds_total = 0.0

    def _token_strings(self) -> List[str]:
        if self._token_strs is None:
            strs = []
            for tid in range(self.vocab_size):
                try:
                    strs.append(self.tokenizer.decode([tid]))
                except Exception:  # noqa: BLE001 — out-of-vocab ids stay unusable
                    strs.append("")
            self._token_strs = strs
        return self._token_strs

    def open(self, spec: dict) -> GuidedState:
        """Compile (or fetch) the spec's token FSM and hand out a fresh
        per-sequence cursor. Raises ValueError (GrammarError) on a bad spec —
        the frontend validates first, so this is the defense line for raw
        engine API users."""
        pattern = spec_to_pattern(spec)
        key = (pattern, id(self.tokenizer), self.vocab_size)

        def build() -> TokenFSM:
            t0 = time.perf_counter()
            fsm = compile_token_fsm(compile_regex(pattern), self._token_strings(), self.eos_ids)
            self.compiles_total += 1
            self.compile_seconds_total += time.perf_counter() - t0
            return fsm

        fsm, cached = self.cache.get(key, build)
        base = self.pool.register(fsm)
        self.requests_total += 1
        return GuidedState(fsm, base, from_cache=cached)

    def stats(self) -> dict:
        return {
            "guided_requests_total": self.requests_total,
            "guided_grammar_compiles_total": self.compiles_total,
            "guided_grammar_compile_seconds_total": round(self.compile_seconds_total, 6),
        }
